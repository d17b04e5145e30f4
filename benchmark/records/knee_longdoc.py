"""The knee table of `brumby14b_longdoc_steady`, by `knee_table.py`'s
method with the limits of a long-document assistant in place of a
chatbot's.

    python benchmark/records/knee_longdoc.py <seconds> <rate>=<file> ... > knee_sweep_longdoc.json

`knee_table.py` holds a request to a first token within 250 ms of being
due: a 4096-token prompt's prefill alone takes longer on this chip, so
no rate would be sustained.  Here a request meets its limits with a
first token within 5 s and later tokens at no more than 150 ms apiece
(a summarisation task's limits in DistServe, arXiv:2401.09670, are 15 s
and 0.15 s as remembered here without a network; 5 s is a third of
that, for a question and not a summary); the rest is the same: 90% of
the requests due inside the window meet both, and the backlog does not
grow.  The knee is the highest sustained rate below the first that is
not.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import knee_table  # noqa: E402

knee_table.TTFT_MS, knee_table.TPOT_MS = 5000.0, 150.0

if __name__ == "__main__":
    knee_table.main(sys.argv[1:])
