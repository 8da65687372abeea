"""submit_us_per_event: host seconds inside ReadoutServer.submit_frames (the
benchmark's own span around each call) per event submitted in the
window."""


def read(rec):
    s = rec["spans"].get("bench.submit_frames")
    if not s or not rec["events_submitted"]:
        return None
    return 1e6 * s["seconds"] / rec["events_submitted"]
