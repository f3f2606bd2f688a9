"""What `cb_live_block_share`, `cb_prefill_fill_share`, the share of
prefills through a flash-kernel rung, the bytes of a block of the paged
kernel's pools (`cb_block_copy_bytes`, a ring's
`cb_window_block_copy_bytes`), the blocks one copy brings
(`cb_extent_blocks`), the copies a live block (`cb_block_copies` /
`cb_live_block_steps`: 1.0 a block a copy, ~0.14 at extents of 8) and
the fill of the grouped matmul's tiles under a cell that chunks
(`cb_grouped_rows` x 3 / `cb_grouped_tile_rows`)
read under one of the benchmark's serving cells (a builder's tool; the
benchmark does not report the counters):

    python tools/live_block_share.py --workload serve-chat-r80 --seed 1 \\
        --seconds 45

runs the cell as `benchmark/run.py` does and prints, when the runner
stops its scheduler, the counters of that engine's `ServeStats` (warm-up
requests included: 4 decode steps of some 11,000, and two 8-token
prompts among the window's prefills)."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run  # noqa: E402
from singa_tpu.serve.scheduler import ContinuousScheduler  # noqa: E402

_stop = ContinuousScheduler.stop


def stop(self, *args, **kwargs):
    snap = self.stats.snapshot()
    print(json.dumps({"tool": "live_block_share", **{
        k: snap[k] for k in ("cb_live_block_share", "cb_prefill_fill_share",
                             "cb_prefills", "cb_flash_prefills",
                             "cb_prefill_rows",
                             "cb_prefill_width_rows", "cb_slot_occupancy",
                             "cb_block_utilization", "cb_steps",
                             "cb_block_bytes", "cb_block_copy_bytes",
                             "cb_window_block_copy_bytes",
                             "cb_extent_blocks", "cb_live_block_steps",
                             "cb_block_copies", "cb_grouped_rows",
                             "cb_grouped_tile_rows")},
        "cb_decode_steps": self.stats.cb_decode_steps,
        "cb_copies_a_live_block": (
            snap["cb_block_copies"] / snap["cb_live_block_steps"]
            if snap["cb_live_block_steps"] else None),
        "cb_grouped_tile_fill": (
            3 * snap["cb_grouped_rows"] / snap["cb_grouped_tile_rows"]
            if snap["cb_grouped_tile_rows"] else None),
        "cb_flash_prefill_share": (
            snap["cb_flash_prefills"] / snap["cb_prefills"]
            if snap["cb_prefills"] else None)}), flush=True)
    return _stop(self, *args, **kwargs)


ContinuousScheduler.stop = stop

if __name__ == "__main__":
    sys.exit(bench_run.main())
