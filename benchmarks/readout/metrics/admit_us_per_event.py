"""admit_us_per_event: host seconds in the server's own ``admit`` span (one
per submit_frames call: admission and enqueue of its events; the span of
ReadoutServer, not the benchmark's) over the window, from
report()["stages"], per event submitted in the window."""


def read(rec):
    s = rec["stages"].get("admit")
    if not s or not s["calls"] or not rec["events_submitted"]:
        return None
    return 1e6 * s["seconds"] / rec["events_submitted"]
