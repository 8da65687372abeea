"""yprofile_roofline: the featurizer kernel's share of its roofline: the
least time the chip needs for the real events' compulsory work (each
frame and y0 read once, the profile written once; work.yprofile_work), over
the kernel's device time in the traced window. The kernel is the Pallas
call of ``yprofile_pallas_stacked``: in the TPU trace it carries no name of
its own (the op is named after the enclosing jit, ``_score_frames_impl.1``)
and is found as the fused step's only Mosaic kernel."""
from readout import work, xplane

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(rec):
    tr = rec["trace"]
    if not tr or not rec["traced_events"] or not rec["peaks"]:
        return None
    t = xplane.op_seconds(tr, KERNEL)
    if t <= 0:
        return None
    ops, nbytes = work.yprofile_work(rec["config"], rec["traced_events"])
    t_min, _ = work.roofline_seconds(ops, nbytes, rec["peaks"])
    return 100.0 * t_min / t
