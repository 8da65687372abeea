"""staging_reuse_pct: the share of the window's frames dispatches that
staged into a reused host arena instead of allocating a fresh buffer,
100 * reused / (reused + fresh), from report()["staging"], whose counts
are reset when the window opens. A program without staging arenas reads
nothing."""


def read(rec):
    s = rec["report"].get("staging")
    if not s or not s["reused"] + s["fresh"]:
        return None
    return 100.0 * s["reused"] / (s["reused"] + s["fresh"])
