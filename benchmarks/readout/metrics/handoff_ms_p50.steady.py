"""handoff_ms_p50.steady: median over the window's batches of the server's
``handoff`` phase (drained to returned by poll() or flush(): what the loop
does after the drain before the answers reach the caller), from
report()["latency"]["phases"]; the server keeps each batch's timestamps,
reset when the window opens."""


def read(rec):
    ph = rec["report"]["latency"].get("phases")
    if not ph or not ph["handoff"]["count"]:
        return None
    return ph["handoff"]["p50_us"] / 1e3
