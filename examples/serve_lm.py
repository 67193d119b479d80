"""Serve a small model with continuous batching (DESIGN.md §13).

Default: the single-device ContinuousEngine — requests admit into any
free slot mid-decode, prompts go into the cache in chunks that ride in
the same step their batch-mates generate in.  Uncomment the mesh/comm-mode args to decode
tensor-parallel over persistent SMI channels (one port claim per layer
tag, held until engine shutdown); add ``--validate-comm`` to byte-check
the ``serve.*`` channel ledger against the netsim prediction instead.

    PYTHONPATH=src python examples/serve_lm.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from repro.launch.serve import main

if __name__ == "__main__":
    main(["--arch", "yi-6b", "--smoke", "--requests", "6",
          "--max-new", "10", "--slots", "3",
          # "--mesh", "1,8", "--comm-mode", "smi:static",
          ])
