"""queue_wait_ms_p50.steady: median of the server's queue-wait (enqueue to coalesce) histogram
(report()["latency"]["queue_wait"], 8 log buckets per decade, so it moves in
steps of about a third), reset when the window opens."""


def read(rec):
    h = rec["report"]["latency"]["queue_wait"]
    if not h["count"]:
        return None
    return h["p50_us"] / 1e3
