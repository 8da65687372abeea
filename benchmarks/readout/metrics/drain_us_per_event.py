"""drain_us_per_event: the server's ``drain_wait`` stage (wait for and fold one dispatch's answers), host seconds
accumulated over the window from report()["stages"], per event drained in
the window."""


def read(rec):
    s = rec["stages"].get("drain_wait")
    if not s or not s["calls"] or not rec["events_in_window"]:
        return None
    return 1e6 * s["seconds"] / rec["events_in_window"]
