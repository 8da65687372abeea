"""serve_mfu_pct: the whole served step's share of the chip's peak while it
runs: the real events answered in the traced window, times each event's
compulsory bytes (its frame and y0, read once), over the device time of the
fused step (``_score_frames_impl``) in that window at the HBM bandwidth
peak. The fabric's work is bitwise and has no published peak, so HBM bytes
bound the step."""
from readout import work

STEP = "_score_frames_impl"


def read(rec):
    tr = rec["trace"]
    if not tr or not rec["traced_events"] or not rec["peaks"]:
        return None
    t = sum(v for k, v in tr["modules"].items() if STEP in k)
    if t <= 0:
        return None
    nbytes = rec["traced_events"] * work.frame_bytes(rec["config"])
    return 100.0 * nbytes / (t * rec["peaks"]["hbm_bytes_per_s"])
