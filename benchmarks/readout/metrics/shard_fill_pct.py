"""shard_fill_pct: the share of the event rows placed on the devices that
carry a real event, 100 * sum(events_per_device) / sum(rows_per_device),
from report()["shards"], whose counts are reset when the window opens.
Padding from uneven modules or a mis-sized max_batch is staged, copied
and computed on every shard. A program without that counter reads
nothing."""


def read(rec):
    s = rec["report"].get("shards")
    if not s or not s["dispatches"] or not sum(s["rows_per_device"]):
        return None
    return 100.0 * sum(s["events_per_device"]) / sum(s["rows_per_device"])
