"""collect_wait_ms_p50.steady: median over the window's batches of the server's
``collect_wait`` phase (launched to the start of its drain: the device step,
then the wait for a poll to collect the batch), from
report()["latency"]["phases"]; the server keeps each batch's timestamps,
reset when the window opens."""


def read(rec):
    ph = rec["report"]["latency"].get("phases")
    if not ph or not ph["collect_wait"]["count"]:
        return None
    return ph["collect_wait"]["p50_us"] / 1e3
