"""Most KV blocks mapped at once over blocks in the arena: the lowest free
watermark any /metrics snapshot of the window shows."""
from _common import in_window


def read(sources):
    snaps = [s for s in in_window(sources, sources.get("snapshots", []))
             if s.get("kv_num_blocks")]
    if not snaps:
        return None
    low = min(min(s.get("kv_free_watermark", s["kv_blocks_free"]), s["kv_blocks_free"])
              for s in snaps)
    return 100.0 * (1.0 - low / snaps[0]["kv_num_blocks"])
