"""service_ms_p50.steady: median of the server's service (coalesce to drained answer, per batch) histogram
(report()["latency"]["service"], 8 log buckets per decade, so it moves in
steps of about a third), reset when the window opens."""


def read(rec):
    h = rec["report"]["latency"]["service"]
    if not h["count"]:
        return None
    return h["p50_us"] / 1e3
