"""Configuration dataclasses for the repro framework.

Everything is a plain frozen dataclass so configs hash/compare cleanly and
can be used as jit static arguments.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    num_shared_experts: int = 0
    top_k: int = 2
    d_ff_expert: int = 0            # expert hidden size (may differ from dense d_ff)
    capacity_factor: float = 1.25
    every_n_layers: int = 1         # apply MoE FFN every n-th layer (1 = all)
    aux_loss_weight: float = 0.01
    # DeepSeek-V2's keys (arXiv:2405.04434, its config.json): whether the
    # top-k gates are renormalised to sum to 1, and the factor on them
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # dropless (DeepSeek-V2): every routed token reaches its experts,
    # computed as grouped matrix products over the tokens sorted by
    # expert (no capacity buffer), with DeepSeek's per-sequence balance
    # loss (its config's `seq_aux`) in place of GShard's
    dropless: bool = False
    # (first, count): the routed experts this device holds, as one chip of
    # an expert-parallel group would; the router still scores all
    # `num_experts`.  None holds them all.
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is not None:
            first, count = self.experts_held
            if not self.dropless:
                raise ValueError("experts_held needs the dropless layer")
            if not (0 <= first and 0 < count
                    and first + count <= self.num_experts):
                raise ValueError(f"experts_held={self.experts_held} is not "
                                 f"a range of the {self.num_experts} "
                                 "experts")

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts held here."""
        return tuple(self.experts_held) if self.experts_held is not None \
            else (0, self.num_experts)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention."""
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN RoPE scaling as DeepSeek-V2 applies it (``rope_scaling`` of
    its config.json, type "yarn")."""
    factor: float = 40.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    original_max_position_embeddings: int = 4096
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-1 style selective SSM (used by jamba)."""
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                # 0 -> ceil(d_model/16)
    attn_every_n: int = 8           # hybrid: 1 attention layer per n layers


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    decay_lora: int = 64            # rank of data-dependent decay LoRA
    shift_lora: int = 32            # rank of data-dependent token-shift LoRA


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    qk_norm: bool = False
    rope_theta: float = 10000.0
    swa_window: int = 0             # 0 = full attention; >0 sliding window
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    yarn: Optional[YarnConfig] = None   # YaRN frequencies on the rope dims
    # enc-dec (whisper): number of encoder layers; encoder input is a stub
    # of precomputed frame embeddings (audio carve-out).
    n_encoder_layers: int = 0
    encoder_seq: int = 0            # fixed encoder frames (whisper: 1500)
    # vlm: number of prefix patch-embedding positions (stub ViT output)
    n_prefix_patches: int = 0
    # §Perf: pad the embedding/vocab rows up to a multiple of 16 so the
    # logits shard over the `model` axis instead of being all-reduced
    # (MaxText-style).  Padded ids are masked to -inf in the loss.
    pad_vocab: bool = False
    dtype: str = "bfloat16"
    # citation for the config (paper / model card)
    source: str = ""

    @property
    def vocab_padded(self) -> int:
        if not self.pad_vocab:
            return self.vocab
        return -(-self.vocab // 16) * 16

    @property
    def attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def subquadratic(self) -> bool:
        """True if the arch natively supports O(<seq^2) long-context decode."""
        return self.arch_type in ("ssm", "hybrid") or self.swa_window > 0


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return (2, 16, 16) if self.multi_pod else (16, 16)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "sgd"          # sgd | momentum | adam
    learning_rate: float = 6.0      # paper's initial step size for RFF model
    momentum: float = 0.0
    weight_decay: float = 0.0
    l2_reg: float = 9e-6            # paper's lambda
    lr_decay: float = 0.8           # paper: step decay 0.8 at epochs 40, 65
    lr_decay_epochs: Tuple[int, ...] = (40, 65)
    epochs: int = 70
    remat: bool = True
    sharding_policy: str = "fsdp_tp"   # fsdp_tp | tp_only | dp_only


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """Federated-learning runtime configuration (paper §V-A defaults)."""
    n_clients: int = 30
    scheme: str = "coded"           # coded | naive | greedy
    psi: float = 0.1                # greedy: wait for (1-psi)*n clients
    delta: float = 0.1              # coded: u_max = delta * m
    # MEC network parameters (paper §V-A)
    max_rate_bps: float = 216e3     # 3 LTE resource blocks
    rate_decay: float = 0.95        # k1
    max_mac_rate: float = 3.072e6   # MAC/s
    mac_decay: float = 0.8          # k2
    alpha: float = 2.0              # compute/memory-access ratio
    p_erasure: float = 0.1          # link erasure probability
    overhead: float = 0.10          # protocol overhead
    bits_per_scalar: int = 32
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class RFFConfig:
    """Paper §V-A kernel embedding hyperparameters."""
    q: int = 2000
    sigma: float = 5.0
    seed: int = 1234


@dataclasses.dataclass(frozen=True)
class LMSpec:
    """A language model trained through the federated round in place of
    the RFF regression (``ExperimentSpec.model``).

    `model` is the model as this device holds it: its
    ``moe.experts_held`` share of the routed experts and, where the
    vocabulary is sliced, its ``vocab`` rows.  Client data is one sequence
    of token ids per client, (n, S + 1); a point is a next-token target,
    and client j's load is the prefix of its first l*_j targets.  Weights
    come from `init_seed`.  The round's gradient is taken over
    `micro_batch` clients at a time (0: all in one backward pass), then
    clipped and applied by AdamW (`repro.core.lm_round`;
    ``TrainConfig.learning_rate`` and ``weight_decay``).
    """
    model: ModelConfig
    init_seed: int = 0
    micro_batch: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "LMSpec":
        d = dict(d)
        m = dict(d["model"])
        for key, typ in (("moe", MoEConfig), ("mla", MLAConfig),
                         ("ssm", SSMConfig), ("rwkv", RWKVConfig),
                         ("yarn", YarnConfig)):
            if isinstance(m.get(key), dict):
                sub = dict(m[key])
                if sub.get("experts_held") is not None:
                    sub["experts_held"] = tuple(sub["experts_held"])
                m[key] = typ(**sub)
        d["model"] = ModelConfig(**m)
        return cls(**d)


_ENGINES = ("batched", "legacy")
_KERNEL_BACKENDS = ("xla", "pallas")
_ALLOC_BACKENDS = ("auto", "scalar", "vectorized")


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One federated experiment, declaratively.

    The spec composes every knob the paper's experiments vary — scheme,
    coding redundancy, delay profile, mesh, kernel/allocation backends —
    into a single frozen, hashable, JSON-serializable value.  Build the
    runnable deployment with ``repro.api.build_experiment(spec, xs, ys)``;
    the spec itself never holds data arrays, so it round-trips through
    ``to_dict``/``from_dict`` bit-exactly and can be logged next to the
    artifacts it produced.

    ``scheme`` names an entry of the scheme registry
    (``repro.core.schemes``); ``None`` defers to ``fl.scheme``.  Scheme
    names are validated at build time against the live registry (schemes
    may be registered after the spec is created), everything else is
    validated here.  ``scheme_params`` carries scheme-specific knobs (e.g.
    the partial-coding ``u_fraction``) as a sorted tuple of pairs so the
    spec stays hashable; pass a plain dict, it is normalized.
    ``delay_profile`` names a heterogeneity profile
    (``repro.core.delay_model.HETEROGENEITY_PROFILES``) whose k1/k2 knobs
    override the matching ``fl`` fields at build time.  ``mesh`` is a
    device count for a 1-D "clients" mesh (a concrete ``jax.sharding.Mesh``
    is not serializable — pass one to ``build_experiment`` directly).

    ``channel_profile`` names a network-dynamics profile
    (``repro.net.channel.CHANNEL_PROFILES``: Gilbert–Elliott erasure
    bursts, shadowing/MCS rate hopping, compute drift, churn) that the
    engine rolls into a deterministic per-seed trace; ``channel_params``
    overrides individual profile knobs (normalized like
    ``scheme_params``).  The ``"static"`` profile reproduces the
    stationary engine bit-exactly.  ``adapt_every`` is the adaptive
    schemes' re-allocation period in rounds (0 = required only by
    adaptive schemes, which reject it).

    ``checkpoint_every`` makes the run block-structured: the round loop
    executes in blocks of that many rounds, each a resume point where the
    full ``RunState`` can be serialized (0 = one block for the whole
    horizon).  For adaptive schemes it must be a multiple of
    ``adapt_every`` (checked at build time) so re-allocation boundaries
    align with blocks.  ``run_id`` optionally names the run for the
    ``ExperimentService`` checkpoint layout.
    """
    fl: FLConfig = FLConfig()
    train: TrainConfig = TrainConfig()
    rff: Optional[RFFConfig] = None
    scheme: Optional[str] = None
    scheme_params: Tuple[Tuple[str, object], ...] = ()
    delay_profile: Optional[str] = None
    channel_profile: Optional[str] = None
    channel_params: Tuple[Tuple[str, object], ...] = ()
    adapt_every: int = 0
    # fault injection (repro.faults): a named FaultProfile whose
    # return-fault knobs (non-finite uploads, stale replay, parity
    # corruption) are injected into the compiled step, and whose
    # infrastructure knobs (block crashes, checkpoint corruption) the
    # ExperimentService consumes.  `fault_params` overrides individual
    # knobs like `channel_params`.  Fault draws come from a dedicated
    # RNG stream, so toggling faults never shifts the delay/channel
    # realization.
    fault_profile: Optional[str] = None
    fault_params: Tuple[Tuple[str, object], ...] = ()
    # jit-compatible non-finite guard: mask faulty client contributions
    # out of the weighted gradient mask (coded schemes: the parity
    # gradient compensates the masked mass).  On a clean run the guard
    # is an IEEE no-op, so trajectories stay bit-identical; disabling it
    # leaves only the always-on theta-divergence round-skip guard.
    nonfinite_guard: bool = True
    engine: str = "batched"
    kernel_backend: str = "xla"
    alloc_backend: str = "auto"
    mesh: Optional[int] = None
    fused_coded: bool = True
    # fused embed->gradient round path: x_stack passed to build_experiment
    # is RAW (n, l, d) features; phi(X) is computed tile-by-tile inside the
    # gradient kernel each round (kernels.rff_linreg_grad) instead of
    # materializing the (n, l, q) embedded tensor up front.  Requires an
    # `rff` config (it supplies q and the shared Omega/delta seed) and the
    # batched engine.
    fused_embed: bool = False
    secure_aggregation: bool = False
    steps_per_epoch: int = 1
    # resumable runtime: rounds per block between checkpoints (0 = run the
    # whole horizon as one block — the one-shot behaviour), and an optional
    # filesystem-safe identity used by the ExperimentService for per-run
    # checkpoint directories
    checkpoint_every: int = 0
    run_id: Optional[str] = None
    # hierarchical population tier (repro.hier): number of edge-aggregator
    # shards the population is partitioned into, and the per-round
    # Bernoulli client-sampling fraction (its draws come from a dedicated
    # RNG stream, so toggling it never shifts the delay realization; the
    # parity gradient is reweighted to compensate the unsampled mass).
    # The identity values (1, 1.0) keep the flat engine — build_experiment
    # only routes to HierExperiment when either departs from identity.
    hier_shards: int = 1
    sample_fraction: float = 1.0
    # a language model in place of the RFF regression (`LMSpec`): the
    # round trains it on token sequences, under the coded scheme's
    # deadline and loads with no parity set (parity coding is linear only)
    model: Optional[LMSpec] = None

    def __post_init__(self):
        if self.engine not in _ENGINES:
            raise ValueError(f"unknown engine {self.engine!r} "
                             f"(expected one of {_ENGINES})")
        if self.kernel_backend not in _KERNEL_BACKENDS:
            raise ValueError(f"unknown kernel_backend "
                             f"{self.kernel_backend!r} "
                             f"(expected one of {_KERNEL_BACKENDS})")
        if self.alloc_backend not in _ALLOC_BACKENDS:
            raise ValueError(f"unknown alloc_backend {self.alloc_backend!r} "
                             f"(expected one of {_ALLOC_BACKENDS})")
        if self.mesh is not None and (not isinstance(self.mesh, int)
                                      or self.mesh < 1):
            raise ValueError(f"mesh must be a positive device count or "
                             f"None, got {self.mesh!r}")
        if self.steps_per_epoch < 1:
            raise ValueError(f"steps_per_epoch must be >= 1, "
                             f"got {self.steps_per_epoch}")
        # normalize scheme_params / channel_params (dict / iterable of
        # pairs) to a sorted tuple of pairs so equal specs hash equal
        # regardless of input form
        for field in ("scheme_params", "channel_params", "fault_params"):
            params = getattr(self, field)
            if isinstance(params, dict):
                items = params.items()
            else:
                items = (tuple(p) for p in params)
            norm = tuple(sorted((str(k), v) for k, v in items))
            object.__setattr__(self, field, norm)
        if self.delay_profile is not None:
            from repro.core.delay_model import HETEROGENEITY_PROFILES
            if self.delay_profile not in HETEROGENEITY_PROFILES:
                raise ValueError(
                    f"unknown delay_profile {self.delay_profile!r} "
                    f"(expected one of "
                    f"{tuple(HETEROGENEITY_PROFILES)})")
        if self.adapt_every < 0:
            raise ValueError(
                f"adapt_every must be >= 0, got {self.adapt_every}")
        if (not isinstance(self.checkpoint_every, int)
                or self.checkpoint_every < 0):
            raise ValueError(f"checkpoint_every must be an int >= 0, "
                             f"got {self.checkpoint_every!r}")
        if self.checkpoint_every > 0 and self.engine == "legacy":
            raise ValueError(
                "checkpoint_every requires the batched engine; the legacy "
                "per-client oracle has no block-structured run state")
        if self.fused_embed:
            if self.rff is None:
                raise ValueError(
                    "fused_embed=True requires an RFFConfig (`rff`): the "
                    "fused kernel derives q and the shared Omega/delta "
                    "frequencies from it")
            if self.engine == "legacy":
                raise ValueError(
                    "fused_embed requires the batched engine; the legacy "
                    "per-client oracle consumes pre-embedded features")
            if self.mesh is not None:
                raise ValueError(
                    "fused_embed does not support client-mesh sharding yet")
        if self.run_id is not None:
            import re
            if not (isinstance(self.run_id, str)
                    and re.fullmatch(r"[A-Za-z0-9][A-Za-z0-9._-]{0,127}",
                                     self.run_id)):
                raise ValueError(
                    f"run_id must be a filesystem-safe slug "
                    f"([A-Za-z0-9._-], not starting with '.'), "
                    f"got {self.run_id!r}")
        if self.channel_profile is not None or self.channel_params:
            from repro.net.channel import CHANNEL_PROFILES
            name = self.channel_profile
            if name is not None and name not in CHANNEL_PROFILES:
                raise ValueError(
                    f"unknown channel_profile {name!r} "
                    f"(expected one of {tuple(CHANNEL_PROFILES)})")
            if self.engine == "legacy":
                raise ValueError(
                    "channel dynamics require the batched engine; the "
                    "legacy per-client oracle has no traced-delay path")
            # knob names (and values, via construction) validated eagerly
            # so the error points at the spec
            self.resolved_channel()
        if not isinstance(self.nonfinite_guard, bool):
            raise ValueError(f"nonfinite_guard must be a bool, "
                             f"got {self.nonfinite_guard!r}")
        if self.fault_profile is not None or self.fault_params:
            from repro.faults.profile import FAULT_PROFILES
            name = self.fault_profile
            if name is not None and name not in FAULT_PROFILES:
                raise ValueError(
                    f"unknown fault_profile {name!r} "
                    f"(expected one of {tuple(FAULT_PROFILES)})")
            if self.engine == "legacy":
                raise ValueError(
                    "fault injection requires the batched engine; the "
                    "legacy per-client oracle has no fault path")
            if self.mesh is not None and self.resolved_faults() is not None \
                    and self.resolved_faults().has_return_faults:
                raise ValueError(
                    "return-fault injection does not support client-mesh "
                    "sharding yet (crash/checkpoint faults are fine)")
            # knob names/values validated eagerly, like channel_params
            self.resolved_faults()
        if not isinstance(self.hier_shards, int) \
                or isinstance(self.hier_shards, bool) or self.hier_shards < 1:
            raise ValueError(f"hier_shards must be an int >= 1, "
                             f"got {self.hier_shards!r}")
        if self.hier_shards > self.fl.n_clients:
            raise ValueError(
                f"hier_shards={self.hier_shards} exceeds "
                f"fl.n_clients={self.fl.n_clients} (each edge-aggregator "
                "shard needs at least one client)")
        if not isinstance(self.sample_fraction, (int, float)) \
                or isinstance(self.sample_fraction, bool) \
                or not 0.0 < float(self.sample_fraction) <= 1.0:
            raise ValueError(f"sample_fraction must lie in (0, 1], "
                             f"got {self.sample_fraction!r}")
        if self.hier_active:
            hier = (f"hier_shards={self.hier_shards}, "
                    f"sample_fraction={self.sample_fraction}")
            if self.engine == "legacy":
                raise ValueError(
                    f"the hierarchical tier ({hier}) requires the batched "
                    "engine; the legacy per-client oracle has no sharded "
                    "round")
            if self.channel_profile is not None or self.channel_params:
                raise ValueError(
                    f"the hierarchical tier ({hier}) has no traced-channel "
                    "path yet; drop channel_profile/channel_params "
                    "(population traces: repro.hier.generate_trace_chunked)")
            if self.fault_profile is not None or self.fault_params:
                raise ValueError(
                    f"the hierarchical tier ({hier}) has no fault-injection "
                    "path yet; drop fault_profile/fault_params")
            if self.adapt_every > 0:
                raise ValueError(
                    f"the hierarchical tier ({hier}) runs the static coded "
                    "round per shard; adaptive re-allocation "
                    f"(adapt_every={self.adapt_every}) is not supported")
            if self.fused_embed:
                raise ValueError(
                    f"the hierarchical tier ({hier}) consumes embedded "
                    "client blocks; fused_embed is not supported")
            if self.secure_aggregation:
                raise ValueError(
                    f"the hierarchical tier ({hier}) does not implement "
                    "secure aggregation of shard rows yet")
            if self.mesh is not None:
                raise ValueError(
                    f"the hierarchical tier ({hier}) shards clients over "
                    "edge aggregators, not a device mesh; drop mesh")

        if self.model is not None:
            if not isinstance(self.model, LMSpec):
                raise ValueError(f"model must be an LMSpec, got "
                                 f"{type(self.model).__name__}")
            unsupported = [name for name, on in (
                ("engine='legacy'", self.engine == "legacy"),
                ("the hierarchical tier", self.hier_active),
                ("channel_profile/channel_params",
                 self.channel_profile is not None
                 or bool(self.channel_params)),
                ("fault_profile/fault_params",
                 self.fault_profile is not None or bool(self.fault_params)),
                ("adapt_every", self.adapt_every > 0),
                ("mesh", self.mesh is not None),
                ("fused_embed", self.fused_embed),
                ("secure_aggregation", self.secure_aggregation),
                ("rff", self.rff is not None)) if on]
            if unsupported:
                raise ValueError(
                    "a model spec (ExperimentSpec.model) trains through the "
                    "flat coded-deadline round only; not combined with "
                    + ", ".join(unsupported))
            if self.model.micro_batch < 0:
                raise ValueError(f"model.micro_batch must be >= 0, got "
                                 f"{self.model.micro_batch}")

    @property
    def hier_active(self) -> bool:
        """True when the spec departs from the flat engine's identity
        configuration and must run on the hierarchical tier."""
        return self.hier_shards > 1 or float(self.sample_fraction) < 1.0

    @property
    def resolved_scheme(self) -> str:
        return self.scheme if self.scheme is not None else self.fl.scheme

    @property
    def scheme_params_dict(self) -> dict:
        return dict(self.scheme_params)

    @property
    def channel_params_dict(self) -> dict:
        return dict(self.channel_params)

    @property
    def fault_params_dict(self) -> dict:
        return dict(self.fault_params)

    def resolved_faults(self):
        """The effective `FaultProfile`, or None when no faults are
        requested.  ``fault_params`` override the named profile's knobs
        (base profile "none" when only overrides are given)."""
        if self.fault_profile is None and not self.fault_params:
            return None
        from repro.faults.profile import FAULT_PROFILES
        base = FAULT_PROFILES[self.fault_profile or "none"]
        if not self.fault_params:
            return base
        try:
            return dataclasses.replace(base, **self.fault_params_dict)
        except TypeError as exc:
            knobs = tuple(f.name for f in dataclasses.fields(base))
            raise ValueError(f"bad fault_params: {exc} "
                             f"(valid knobs: {knobs})") from None

    def resolved_channel(self):
        """The effective `ChannelProfile`, or None when no dynamics are
        requested.  ``channel_params`` override the named profile's knobs
        (base profile "static" when only overrides are given)."""
        if self.channel_profile is None and not self.channel_params:
            return None
        from repro.net.channel import CHANNEL_PROFILES
        base = CHANNEL_PROFILES[self.channel_profile or "static"]
        if not self.channel_params:
            return base
        try:
            return dataclasses.replace(base, **self.channel_params_dict)
        except TypeError as exc:
            knobs = tuple(f.name for f in dataclasses.fields(base))
            raise ValueError(f"bad channel_params: {exc} "
                             f"(valid knobs: {knobs})") from None

    def resolved_fl(self) -> FLConfig:
        """`fl` with the named delay profile's knobs applied."""
        if self.delay_profile is None:
            return self.fl
        from repro.core.delay_model import HETEROGENEITY_PROFILES
        return dataclasses.replace(
            self.fl, **HETEROGENEITY_PROFILES[self.delay_profile])

    # ------------------------------------------------------------- round trip
    def to_dict(self) -> dict:
        """Plain-JSON dict; `from_dict(to_dict(spec)) == spec`."""
        d = dataclasses.asdict(self)
        d["scheme_params"] = dict(self.scheme_params)
        d["channel_params"] = dict(self.channel_params)
        d["fault_params"] = dict(self.fault_params)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "ExperimentSpec":
        d = dict(d)
        for key, typ in (("fl", FLConfig), ("train", TrainConfig),
                         ("rff", RFFConfig)):
            val = d.get(key)
            if isinstance(val, dict):
                val = dict(val)
                # JSON has no tuples; restore the tuple-typed fields
                for tup_field in ("lr_decay_epochs",):
                    if tup_field in val and val[tup_field] is not None:
                        val[tup_field] = tuple(val[tup_field])
                d[key] = typ(**val)
        if isinstance(d.get("model"), dict):
            d["model"] = LMSpec.from_dict(d["model"])
        valid = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - valid
        if unknown:
            raise ValueError(
                f"unknown ExperimentSpec field(s) {sorted(unknown)} "
                f"(valid fields: {sorted(valid)})")
        return cls(**d)
