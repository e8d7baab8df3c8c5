"""A run of each traffic kind with the timed path broken underneath must
read ``correct`` false against the committed limits; the same run unbroken
reads true.  Runs the harness past its look for a chip, on the CPU, at a
small size (the chip readings of the same faults at the cells' own sizes
come from ``bench/readings.py --fault``)."""
import json
import os
import time

import pytest

from benchlib import faults
from benchlib.cells import run_cell
from test_reference import conf

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIVE = {"kind": "open_loop", "sessions": 4, "frame_hz": 30.0, "qos": "fifo",
        "lead_in_s": 1.6, "wait_for_window": True, "drain_s": 10.0,
        "reads_per_session": 2}
CLIP = {"kind": "clip", "clips_per_batch": 2, "persons": 2, "batches": 2}
CELL = {"live": (LIVE, "pruned-live"), "clip": (CLIP, "pruned-clip")}


def run(kind, seconds=1.0):
    tr, cell = CELL[kind]
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        limits = json.load(f)
    return run_cell(conf(), tr, limits, 2 ** 31 + 99, seconds, False, "",
                    lambda m: None, time.monotonic())


@pytest.mark.parametrize("kind", ["live", "clip"])
def test_sound_run_is_correct(kind):
    res = run(kind)
    assert res["correct"], res["compared"]
    assert res["compared"]["logit_gap"]["value"] < 1e-4


@pytest.mark.parametrize("kind,fault", [
    ("live", "answer"), ("live", "state"), ("live", "half"),
    ("clip", "answer"), ("clip", "half")])
def test_broken_step_reads_incorrect(monkeypatch, kind, fault):
    faults.plant(fault, monkeypatch.setattr)
    res = run(kind)
    assert not res["correct"]
    gap = res["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]
