"""Elastic integration-test worker (reference pattern:
test/integration/data/elastic_torch_main.py — record epoch/commit/rank
history to a JSON-lines file for the test to assert on; inject failures
via a marker file naming the host that should die)."""

import json
import os
import sys
import time

import horovod_tpu as hvd
from horovod_tpu.runner import elastic_worker

LOG_PATH = os.path.join(
    os.environ["TEST_LOG_DIR"],
    "worker-{}-{}.jsonl".format(
        os.environ.get("HOROVOD_HOSTNAME", "localhost"),
        os.environ.get("HOROVOD_SLOT", "0")),
)


def record(event, state):
    with open(LOG_PATH, "a") as f:
        f.write(json.dumps({
            "event": event,
            "epoch": getattr(state, "epoch", -1),
            "rank": int(os.environ.get("HOROVOD_RANK", -1)),
            "size": int(os.environ.get("HOROVOD_SIZE", -1)),
            "gen": elastic_worker._known_gen,
        }) + "\n")


def maybe_fail(state):
    marker = os.environ.get("FAIL_MARKER")
    if marker and os.path.exists(marker):
        with open(marker) as f:
            target = f.read().strip()
        if target == os.environ.get("HOROVOD_HOSTNAME"):
            record("failing", state)
            sys.exit(1)


hvd.init()
state = hvd.elastic.ObjectState(epoch=0)


@hvd.elastic.run
def train(state):
    num_epochs = int(os.environ.get("NUM_EPOCHS", "5"))
    epoch_time = float(os.environ.get("EPOCH_TIME", "0.5"))
    while state.epoch < num_epochs:
        maybe_fail(state)
        time.sleep(epoch_time)
        state.epoch += 1
        record("commit", state)
        state.commit()
    record("done", state)


train(state)
record("exit", state)
