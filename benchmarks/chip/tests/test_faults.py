"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run (set-up, window, check) at a small size on
the CPU, skipping only the harness's look for a chip, with
`Replica.resolve` intact or broken in one of the ways a merge round can
go wrong: the state returned unchanged, half of the visible set left
out, one merged value altered where it is produced. (The cells run on
one chip: there is no exchange between chips to leave out.)

Besides the mixes of the cells, it runs each shape of round that a
traffic file can ask for: append only, sparse contributions against a
pinned base, a durable replica, and a pair of replicas kept in sync.
"""
import time
from unittest import mock

import jax
import pytest

import harness
from conftest import TINY_CONFIG
from repro.api import Replica


def _stale(orig):
    first = []

    def resolve(self, spec, **kw):
        if not first:
            first.append(orig(self, spec, **kw))
        return first[0]
    return resolve


def _half(orig):
    def resolve(self, spec, **kw):
        half = self.state
        for eid in sorted(half.visible())[::2]:
            half = half.remove(eid, "fault")
        with mock.patch.object(Replica, "state",
                               property(lambda _: half)):
            return orig(self, spec, **kw)
    return resolve


def _altered(orig):
    def resolve(self, spec, **kw):
        out = orig(self, spec, **kw)
        leaves, treedef = jax.tree_util.tree_flatten(out)
        leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(0.25)
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return resolve


FAULTS = {"sound": None, "stale": _stale, "half": _half,
          "altered": _altered}

_LIMITS = {"layer1_mismatches": 0, "merged_gap": 0.0625}
SHAPES = {
    "append": {"strategy": "weight_average", "cfg": {}, "k": 2,
               "pin_base": False, "delta_std": 0.002, "retract": False,
               "limits": _LIMITS},
    "sparse": {"strategy": "task_arithmetic", "cfg": {}, "k": 2,
               "pin_base": True, "delta_std": 0.002, "retract": False,
               "leaves": ["w_in", "a_log"], "limits": _LIMITS},
    "durable": {"strategy": "weight_average", "cfg": {}, "k": 3,
                "pin_base": False, "delta_std": 0.002,
                "replica": "durable", "limits": _LIMITS},
    "sync_pair": {"strategy": "ties", "cfg": {}, "k": 3, "pin_base": True,
                  "delta_std": 0.002, "replica": "sync_pair",
                  "limits": _LIMITS},
}
MIXES = ["wa_window", "ties_window", *SHAPES]


def _cell(mix):
    traffic = (harness.check_traffic(SHAPES[mix]) if mix in SHAPES
               else harness.load_traffic(mix))
    return {"name": mix, "chips": 1, "config": TINY_CONFIG,
            "traffic": traffic}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("mix", MIXES)
def test_run_is_correct_only_when_sound(monkeypatch, mix, fault):
    if FAULTS[fault] is not None:
        monkeypatch.setattr(Replica, "resolve",
                            FAULTS[fault](Replica.resolve))
    rec = harness.run_cell(_cell(mix), 2 ** 31 + 5, 0.3, False,
                           t_start=time.perf_counter(),
                           check_device=False, log=lambda s: None)
    assert rec["rounds"]
    assert rec["correct"] is (fault == "sound")
    assert (rec["failed"] == 0) is (fault == "sound")


def test_traffic_that_says_another_shape_is_refused():
    with pytest.raises(ValueError):
        harness.check_traffic(dict(SHAPES["append"], retrac=False))
    with pytest.raises(ValueError):
        harness.check_traffic(dict(SHAPES["append"],
                                   limits={"merged_gap": None}))
    with pytest.raises(ValueError):
        harness.check_traffic(dict(SHAPES["append"], replica="cluster"))
