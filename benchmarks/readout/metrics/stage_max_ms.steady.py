"""stage_max_ms.steady: the longest single call of any of the server's
spans (admit, stack_frames, launch_fused, drain_wait, ...) in the window,
from report()["stages"][*]["max_s"], reset when the window opens. A host
stall inside the server shows here; one outside it does not."""


def read(rec):
    longest = [s["max_s"] for s in rec["report"]["stages"].values()
               if "max_s" in s]
    if not longest:
        return None
    return 1e3 * max(longest)
