"""Declarative experiment API: one entrypoint for every workload shape.

The paper's experiments are a handful of knobs — scheme, coding
redundancy, load allocation, delay profile, backends.  `ExperimentSpec`
(repro.config) freezes those knobs into one JSON-serializable value, the
scheme registry (repro.core.schemes) makes the straggler-mitigation
strategy pluggable, and `build_experiment` turns spec + data into a
runnable `Experiment` whose ``.run`` / ``.run_multi`` / ``.sweep`` all
flow through the shared compiled-step machinery
(`Experiment.build_consts`, `fed_runtime.build_step` and the scheme's
`round_rule`).

    from repro.api import ExperimentSpec, build_experiment
    from repro.config import FLConfig, TrainConfig

    spec = ExperimentSpec(
        fl=FLConfig(n_clients=12, delta=0.2),
        train=TrainConfig(learning_rate=0.5),
        scheme="partial_coded",
        scheme_params={"u_fraction": 0.3},
        delay_profile="paper",
        kernel_backend="pallas",
    )
    exp = build_experiment(spec, xs, ys)
    result = exp.run(100)

    # specs round-trip through JSON for logging / artifact provenance
    same = ExperimentSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert same == spec
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from repro.config import ExperimentSpec
from repro.core import schemes
from repro.core.fed_runtime import (Experiment, FedResult,  # noqa: F401
                                    MultiFedResult, RoundLog, RunHealth)
from repro.core.run_state import RunState  # noqa: F401
from repro.core.schemes import (Scheme, get_scheme, grid_names,  # noqa: F401
                                register, registered_names)
from repro.faults import (FAULT_PROFILES, FaultProfile,  # noqa: F401
                          get_fault_profile)
from repro.net.channel import (CHANNEL_PROFILES,  # noqa: F401
                               ChannelProfile)
from repro.obs import (Attribution, RunJournal,  # noqa: F401
                       histories_equal, history_from_journal, load_events)
from repro.obs import spans as obs_spans  # noqa: F401

__all__ = [
    "ExperimentSpec", "Experiment", "ExperimentService", "FedResult",
    "MultiFedResult", "RoundLog", "RunHealth", "RunState", "Scheme",
    "build_experiment", "get_scheme", "grid_names", "register",
    "registered_names", "CHANNEL_PROFILES", "ChannelProfile",
    "FAULT_PROFILES", "FaultProfile", "get_fault_profile",
    "Attribution", "RunJournal", "load_events", "history_from_journal",
    "histories_equal", "obs_spans",
]


def __getattr__(name):
    # lazy: launch.service imports build_experiment from here, so a
    # top-level import would be circular
    if name == "ExperimentService":
        from repro.launch.service import ExperimentService
        return ExperimentService
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def build_experiment(spec: "ExperimentSpec | dict", x_stack=None,
                     y_stack=None, *,
                     nodes: Optional[list] = None,
                     rng: Optional[np.random.Generator] = None,
                     mesh=None, data_fn=None):
    """Build a runnable `Experiment` from a spec and client data.

    spec: an `ExperimentSpec` (or its `to_dict()` form, revived here);
    x_stack: (n, l, q) RFF-embedded client features — or, with
    ``spec.fused_embed=True``, the RAW (n, l, d) features (the embedding
    then happens inside the per-round gradient kernel, parameterized by
    ``spec.rff``); y_stack: (n, l, c) targets.  With ``spec.model`` (a
    language model, `repro.core.lm_round`) x_stack is the clients' token
    ids, (n, S + 1), and y_stack is None.  `nodes` / `rng` override the delay network and the host RNG
    (both default to the spec's seeds, so equal specs reproduce equal
    deployments).  `mesh` accepts a concrete 1-D "clients"
    `jax.sharding.Mesh` (not serializable, hence not a spec field) or a
    device count, overriding ``spec.mesh``.

    Specs with ``hier_shards > 1`` or ``sample_fraction < 1.0`` build a
    `repro.hier.HierExperiment` instead (edge-aggregator shards, sampled
    cohorts with coded compensation); those may stream client blocks via
    ``data_fn(lo, hi) -> (x, y)`` in place of dense stacks, so a
    population of 1e5-1e6 clients never materializes an (n, l, q)
    tensor.  The identity configuration (``hier_shards=1,
    sample_fraction=1.0``) always takes the flat engine, so its
    trajectories are bit-identical to the pre-hier runtime.
    """
    if isinstance(spec, dict):
        spec = ExperimentSpec.from_dict(spec)
    # validate the scheme against the live registry up front so the error
    # points at the spec, not at a stack frame deep in Experiment setup
    schemes.get_scheme(spec.resolved_scheme)
    if spec.hier_active:
        from repro.hier import HierExperiment
        if nodes is not None or mesh is not None:
            raise ValueError(
                "the hierarchical tier builds its delay population from "
                "the spec (repro.hier.population_delay_arrays) and shards "
                "clients over edge aggregators; nodes/mesh overrides are "
                "not supported with hier_shards > 1 or "
                "sample_fraction < 1.0")
        return HierExperiment(spec, x_stack, y_stack, data_fn=data_fn,
                              rng=rng)
    if data_fn is not None:
        raise ValueError(
            "data_fn streaming is only supported by the hierarchical tier "
            "(hier_shards > 1 or sample_fraction < 1.0); the flat engine "
            "takes dense x_stack/y_stack")
    return Experiment(spec, x_stack, y_stack, nodes=nodes, rng=rng,
                      mesh=mesh)
