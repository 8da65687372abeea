"""latency_p50_ms: median, over every event due in the window, of the time
from its scheduled arrival to its drained answer (host clock); an event
never answered counts with the time waited so far. Open-loop cells
only."""
import numpy as np


def read(rec):
    if rec["traffic"]["kind"] != "open_loop" or not len(rec["latency_s"]):
        return None
    return 1e3 * float(np.median(rec["latency_s"]))
