"""events_per_s: events whose answers drained inside the window, over the
window's seconds (host clock). Closed-loop cells only."""


def read(rec):
    if rec["traffic"]["kind"] != "closed_loop":
        return None
    return rec["events_in_window"] / rec["window_s"]
