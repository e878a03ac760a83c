"""The coded round of RFF kernel regression (the paper's Sec. V-A, and
its hierarchical tier) as a kind of cell: the kind of every
configuration that names none.

* inputs: the clients' RFF features and one-hot labels
  (``bench/generate.py``), made from the benchmark's seed;
* system: the deployment built through ``repro.api.build_experiment``
  (``bench/system.py``); its snapshot is the iterate theta (q x c), and
  what the tail hands the reference is the set-up layer's answers (t*,
  loads, the parity set times a fixed probe), the iterate after the
  tail and every round's count of clients back by the deadline;
* comparison: the reference (``bench/reference.py``) replays set-up and
  the first calls, draws the window's calls without playing them, then
  plays the tail from the program's iterate at the window's close;
  ``bench/compare.py`` reduces both sides to the compared numbers;
* work count: the rows the window's rounds needed
  (``bench/workcount.py``), with q and c, under ``rows``, ``q``, ``c``.
"""
from __future__ import annotations

import numpy as np

import compare
import generate
import system
import workcount
from reference import Arith, Data, Reference

#: reference variants that can be put in the program's place, compared
#: exactly as the program is (``bench/calibrate.py``, tests)
VARIANTS = {"reference": {}, "control": {"control": True},
            "bf16": {"operands": "bf16"},
            "half_batch": {"fault": "half_batch"},
            "cursor_drift": {"fault": "cursor_drift"}}


def make_inputs(cfg: dict, traffic: dict, seed: int) -> dict:
    """The program's seed and the clients' (x, y): device arrays, or host
    arrays for the hierarchical tier, which streams its clients."""
    fl_seed, data_seed = generate.seeds(seed)
    x, y = generate.make_data(cfg, data_seed, fl_seed,
                              host=generate.hierarchical(cfg))
    return {"fl_seed": fl_seed, "x": x, "y": y}


class System(system.System):
    """`system.System` built from this kind's inputs."""

    def __init__(self, cfg: dict, traffic: dict, inputs: dict):
        super().__init__(cfg, traffic, inputs["fl_seed"], inputs["x"],
                         inputs["y"])
        self.q = cfg["q"]

    def snapshot(self) -> np.ndarray:
        return self.theta()

    def handoff(self) -> dict:
        """Host copies of the set-up answers, the iterate and the
        per-round returns, taken after the tail."""
        return {"setup": compare.probed(self.answers(), Arith().mm,
                                        compare.probe(self.q)),
                "theta": self.theta(), "returned": self.returned()}


def check(cfg: dict, traffic: dict, inputs: dict, ran: dict, limits: dict,
          variants=()) -> tuple[bool, dict, dict, dict]:
    """(correct, checks, {variant: numbers}, ctx entries) of one run:
    `ran` holds the program's snapshots after the first calls
    (``warm``), at the window's close (``window_end``), the window's
    call count (``calls``) and the tail's `System.handoff`
    (``tail``)."""
    steps = len(ran["warm"])
    fl_seed, theta_w = inputs["fl_seed"], ran["window_end"]
    net = generate.network(cfg, fl_seed)
    data = Data(inputs["x"], inputs["y"])
    ar = Arith()
    probe = compare.probe(cfg["q"])
    tail = ran["tail"]
    sp = compare.side(tail["setup"], ran["warm"], tail["theta"],
                      tail["returned"])

    def replay(**variant):
        r = Reference(cfg, traffic, fl_seed, net, data, **variant)
        setup = compare.probed(r.shards, ar.mm, probe)
        thetas = r.run(steps)
        back = r.skip(ran["calls"])
        tail = r.run(steps, theta_w)[-1]
        return r, compare.side(setup, thetas, tail,
                               np.concatenate(r.returned)), back

    ref, sr, back = replay()
    correct, checks = compare.judge(
        compare.numbers(sp, sr, theta_w, ref.losses), limits)
    others = {name: compare.numbers(replay(**VARIANTS[name])[1], sr,
                                    theta_w, ref.losses)
              for name in variants}
    rows = workcount.needed_rows(back, ref.loads,
                                 sum(sh["u"] for sh in ref.shards))
    return correct, checks, others, {"rows": rows, "q": cfg["q"],
                                     "c": cfg["classes"]}
