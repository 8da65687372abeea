"""staging_ms_p50.steady: median over the window's batches of the server's
``staging`` phase (coalesced to launched: host staging and the dispatch of
the fused step), from report()["latency"]["phases"]; the server keeps each
batch's timestamps, reset when the window opens."""


def read(rec):
    ph = rec["report"]["latency"].get("phases")
    if not ph or not ph["staging"]["count"]:
        return None
    return ph["staging"]["p50_us"] / 1e3
