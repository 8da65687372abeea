"""place_us_per_event: the server's ``place_frames`` stage (pad the staged
batch and device_put it onto its chip-axis shards, one per device), host
seconds accumulated over the window from report()["stages"], per event
drained in the window. The stage is timed inside ``launch_fused``. A
program without that span reads nothing."""


def read(rec):
    s = rec["stages"].get("place_frames")
    if not s or not s["calls"] or not rec["events_in_window"]:
        return None
    return 1e6 * s["seconds"] / rec["events_in_window"]
