"""fused_step_ns_per_event: device time of the fused frames -> trigger step
(the jit of kernels/frontend.py's ``_score_frames_impl``) in the traced
window, per real (unpadded) event answered in that window."""
STEP = "_score_frames_impl"


def read(rec):
    tr = rec["trace"]
    if not tr or not rec["traced_events"]:
        return None
    t = sum(v for k, v in tr["modules"].items() if STEP in k)
    if t <= 0:
        return None
    return 1e9 * t / rec["traced_events"]
