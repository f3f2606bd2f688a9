#!/usr/bin/env python3
"""`benchmark/probe.py` for the cells whose runner is `serve_kimi`: the
same sweep and seeds readings, with that runner's engine under the
sweep (probe.py's `sweep` builds through `serve_cb.build`, looked up
when it is called).

    python3 benchmark/probe_kimi.py sweep --workload W --rates 3,4,5 --seconds 45 --out F
    python3 benchmark/probe_kimi.py seeds --workload W --seeds 11,12 --seconds 45 [--control fp8|state_bf16] --out F
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import probe                     # noqa: E402


def main() -> int:
    from benchmark.runners import serve_cb
    real = serve_cb.build

    def build(cell, seed):
        from benchmark.runners import serve_kimi
        if cell.spec["runner"] == "serve_kimi":
            return serve_kimi.build(cell, seed)
        return real(cell, seed)

    serve_cb.build = build
    return probe.main()


if __name__ == "__main__":
    sys.exit(main())
