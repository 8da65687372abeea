"""launch_us_per_event: the server's ``launch_fused`` stage (pad, device_put and dispatch of the fused step), host seconds
accumulated over the window from report()["stages"], per event drained in
the window."""


def read(rec):
    s = rec["stages"].get("launch_fused")
    if not s or not s["calls"] or not rec["events_in_window"]:
        return None
    return 1e6 * s["seconds"] / rec["events_in_window"]
