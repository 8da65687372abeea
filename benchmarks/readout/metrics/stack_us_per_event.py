"""stack_us_per_event: the server's ``stack_frames`` stage (zero-fill and stack the frames of one dispatch), host seconds
accumulated over the window from report()["stages"], per event drained in
the window."""


def read(rec):
    s = rec["stages"].get("stack_frames")
    if not s or not s["calls"] or not rec["events_in_window"]:
        return None
    return 1e6 * s["seconds"] / rec["events_in_window"]
