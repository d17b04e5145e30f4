"""Worker main for the REAL multi-process chaos soak (docs/CHAOS.md).

Launched by the runner with -np 2 (fast tier-1 variant) or -np 4 (slow
soak): every rank runs the same `ChaosSoak` — fault-loaded eager
training with per-generation merged-trace windows, the straggler
reaction policy, and the online autotuner — and writes the soak's
JSON-serializable result to $HVD_TEST_OUT/rank{r}.json for the test to
assert on (events all recovered, no split brain, reaction fired,
autotune best non-worsening, final params bitwise-identical).

Soak shape comes from the standard env knobs
(HOROVOD_CHAOS_GENERATIONS / HOROVOD_CHAOS_STEPS_PER_GEN /
HOROVOD_STRAGGLER_*) plus HVD_CHAOS_SEED, so the launching test
controls the plan deterministically.  HVD_CHAOS_STALL_MS and
HVD_CHAOS_STRAGGLER_DELAY_MS (this worker's own, like the seed) size
the injected delays: a test that runs beside others gives them a margin
over the machine's noise.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.faults.chaos import ChaosSoak  # noqa: E402


def main():
    hvd.init()
    delays = {arg: int(os.environ[var]) for arg, var in (
        ("stall_ms", "HVD_CHAOS_STALL_MS"),
        ("straggler_delay_ms", "HVD_CHAOS_STRAGGLER_DELAY_MS"))
        if var in os.environ}
    soak = ChaosSoak(seed=int(os.environ.get("HVD_CHAOS_SEED", "7")),
                     **delays)
    res = soak.run()
    out_dir = os.environ["HVD_TEST_OUT"]
    with open(os.path.join(out_dir, f"rank{hvd.rank()}.json"), "w") as f:
        json.dump(res, f)
    hvd.shutdown()


if __name__ == "__main__":
    main()
