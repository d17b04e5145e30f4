"""The knee table of `gigachat702b_longctx_steady`, by `knee_table.py`'s
method with `knee_longdoc.py`'s limits: a request meets them with a first
token within 5 s of being due and later tokens at no more than 150 ms
apiece; 90% of the requests due inside the window meet both, and the
backlog does not grow.  The knee is the highest sustained rate below the
first that is not; the cell offers four fifths of it, rounded to 0.1.

    for r in 0.8 1.0 1.2 1.4; do
      sed -i "s/\\"rate_per_s\\": [0-9.]*/\\"rate_per_s\\": $r/" \\
          benchmark/traffic/longctx_steady.json
      python3 benchmark/run.py --workload gigachat702b_longctx_steady \\
          --seed <n> --seconds 40 --trace 0 --timeline tl_$r.json
    done
    python benchmark/records/knee_longctx.py 40 0.8=tl_0.8.json ... \\
        > benchmark/records/knee_sweep_longctx.json

(the traffic file is edited in the chip's throw-away copy, a seed a rate).
A prompt here is 4096 to 16384 tokens and its prefill alone 0.16 to
0.86 s (records/probe_latent_prefill.py), so a chatbot's 250 ms to the
first token would sustain no rate, as for the long documents.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import knee_table  # noqa: E402

knee_table.TTFT_MS, knee_table.TPOT_MS = 5000.0, 150.0

if __name__ == "__main__":
    knee_table.main(sys.argv[1:])
