"""setup_s: seconds from the start of the benchmark process to the first
timed event (host clock): imports, device start, classifiers trained from
the seed, frame pool, server, and one dispatch of every batch shape the
traffic uses (compiled, or loaded from the persistent compile cache)."""


def read(rec):
    return rec["setup_s"]
