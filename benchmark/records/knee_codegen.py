"""The knee table of `laguna_xs2_codegen_steady`, by `knee_table.py`'s
method with the limits of a code assistant in place of a chatbot's.

    python benchmark/records/knee_codegen.py <seconds> <rate>=<file> ... > knee_sweep_codegen.json

`knee_table.py` holds a request to a first token within 250 ms of being
due: a 6144-token prompt's prefill alone takes about that on this chip
behind whatever is prefilled before it.  Here a request meets its limits
with a first token within 2 s (a developer waits for a completion, not a
keystroke's echo) and later tokens at no more than 50 ms apiece (20
tokens/s: faster than a patch is read); the rest is the same: 90% of the
requests due inside the window meet both, and the backlog does not grow.
The knee is the highest sustained rate below the first that is not.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import knee_table  # noqa: E402

knee_table.TTFT_MS, knee_table.TPOT_MS = 2000.0, 50.0

if __name__ == "__main__":
    knee_table.main(sys.argv[1:])
