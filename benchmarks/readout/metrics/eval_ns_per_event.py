"""eval_ns_per_event: device time of the fused step less the featurizer
kernel's (quantize, bit pack, bit-sliced fabric evaluation, TMR vote, score
decode and cut; also the frames' relayout and padding), per real event
answered in the traced window."""
from readout import xplane

STEP = "_score_frames_impl"
KERNEL = 'custom_call_target="tpu_custom_call"'  # the featurizer, see
# yprofile_roofline.py


def read(rec):
    tr = rec["trace"]
    if not tr or not rec["traced_events"]:
        return None
    step = sum(v for k, v in tr["modules"].items() if STEP in k)
    kern = xplane.op_seconds(tr, KERNEL)
    if step <= 0 or kern <= 0:
        return None
    return 1e9 * (step - kern) / rec["traced_events"]
