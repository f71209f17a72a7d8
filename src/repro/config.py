"""Configuration system.

Plain dataclasses (no external deps), a registry keyed by ``--arch`` id, and
the four assigned input shapes. Every architecture config module in
``repro.configs`` registers itself at import via :func:`register_arch`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple


# --------------------------------------------------------------------------
# Model configuration
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    """Frozen pure data — hashable, safe jit cache-key material."""
    num_experts: int = 0            # routed experts (0 = dense MLP)
    num_shared_experts: int = 0     # always-on experts (DeepSeek style)
    top_k: int = 2
    aux_loss_weight: float = 0.01   # router load-balance loss
    # "ragged": sort + grouped GEMM (ragged_dot) — exact, no drops, but
    #   GSPMD cannot partition the global sort (per-layer all-reduce of the
    #   full activation — see EXPERIMENTS §Perf iter 2b).
    # "gshard": capacity-based one-hot dispatch einsums — expert-parallel
    #   friendly (dispatch lowers to all-to-all-ish movement), token drops
    #   beyond capacity_factor.
    impl: str = "ragged"
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class ModelConfig:
    """Frozen pure data describing one architecture; hashable — models build deterministically from it."""
    name: str = "model"
    family: str = "dense"           # dense | moe | hybrid | ssm | vlm | audio | lenet
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0               # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    max_seq_len: int = 8192
    # attention
    qkv_bias: bool = False          # Qwen2-style
    sliding_window: int = 0         # 0 = full attention
    rope_theta: float = 10000.0
    # MoE
    moe: MoEConfig = field(default_factory=MoEConfig)
    # MLA (DeepSeek-V2): 0 disables, >0 is the KV LoRA/latent rank
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 64         # decoupled rope dims for MLA
    # hybrid (RecurrentGemma / Griffin): block pattern, e.g. ("rec","rec","attn")
    block_pattern: Tuple[str, ...] = ()
    rglru_dim: int = 0              # 0 -> d_model
    local_attn_window: int = 2048
    # xLSTM
    mlstm_ratio: int = 7            # mLSTM blocks per sLSTM block
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq_len: int = 1500     # stubbed frame-embedding length
    # VLM stub frontend
    num_image_patches: int = 0      # prepended patch embeddings per sample
    # training-path memory control
    attn_impl: str = "auto"         # naive | chunked | auto (chunked iff S >= chunk)
    chunk_size: int = 512
    # norms / activations
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    act: str = "silu"
    dtype: Any = "bfloat16"
    # LeNet (radar) specific
    input_hw: Tuple[int, int] = (0, 0)
    num_classes: int = 0
    # layer scanning for deep stacks
    scan_layers: bool = True

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Federated / CD-BFL configuration (the paper's knobs)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class TopologyConfig:
    """Device graph of the D2D deployment (DESIGN.md §4).

    Generators live in ``repro.core.topology``; this is pure data so config
    stays dependency-free. The static fields pick the graph family and its
    parameters; the last two make Ω time-varying (per-round realizations are
    drawn *inside* the jitted round from a PRNG key, so rounds stay pure).
    """
    graph: str = "full"             # full | ring | chain | star | grid |
                                    # torus | k_regular | erdos_renyi | geometric
    degree: int = 4                 # k_regular: even neighbor count
    edge_prob: float = 0.3          # erdos_renyi: iid link probability
    radius: float = 0.45            # geometric: radio range in the unit square
    rule: str = "metropolis"        # metropolis | max_degree | uniform
    seed: int = 0                   # graph-sampling seed (ER / geometric)
    # time-varying schedule (0/0 = static graph)
    link_failure_prob: float = 0.0  # per-round, per-link Bernoulli dropout
    gossip_pairs: int = 0           # >0: activate only this many matchings/round

    def replace(self, **kw) -> "TopologyConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class TransportConfig:
    """Lossy D2D frame transport under the gossip layer (DESIGN.md §11).

    Payloads are fragmented into ``mtu``-bounded frames (8-byte LEN/SEQ/CRC
    header each); frames erase per the named loss model, and whole links
    drop for a round per the SNR-derived Rayleigh outage (reusing the
    gossip layer's ``link_failure_prob`` seam). Pure data so config stays
    dependency-free; ``repro.core.transport`` interprets it.
    """
    mtu: int = 256                  # on-air frame size cap, header included
    # per-frame erasure: scalar rate, or a per-node tuple (asymmetric loss;
    # 1.0 = dead transmitter). Interpreted by the ``loss_model`` below.
    erasure: Any = 0.0
    loss_model: str = "bernoulli"   # bernoulli | gilbert
    # Gilbert-Elliott burst channel (loss_model="gilbert")
    gilbert_p_enter: float = 0.05   # good -> bad episode start, per frame
    gilbert_p_exit: float = 0.3     # bad -> good recovery, per frame
    gilbert_loss_good: float = 0.0
    gilbert_loss_bad: float = 1.0
    # SNR-parameterized per-link outage (None disables): per-node mean SNR
    # snr_db ± lognormal shadowing, edge outage 1 - exp(-γ_th/γ̄) at the
    # weaker endpoint, fed into the gossip link-dropout seam.
    snr_db: Optional[float] = None
    snr_spread_db: float = 0.0
    snr_threshold_db: float = 0.0
    # radio cost model (802.15.4-class defaults) for airtime/energy columns
    phy_rate_bps: float = 250_000.0
    tx_power_w: float = 0.1
    # selective-repeat ARQ (DESIGN.md §12): lost frames are retransmitted
    # up to ``max_retries`` extra attempts, each attempt drawing a fresh
    # PRNG-pure keep mask (fold_in of the per-leaf transport key by the
    # attempt index). ``arq_backoff_s`` is the wait before retransmit
    # attempt a (doubling per attempt), charged against the round's
    # airtime budget but not TX energy. arq=False keeps the single-shot
    # path bitwise identical to the pre-ARQ transport.
    arq: bool = False
    max_retries: int = 2
    arq_backoff_s: float = 0.0
    # LoRa-style time-on-air accounting (DESIGN.md §12): per-frame airtime
    # from the SX127x symbol-count formula at spreading factor ``sf`` over
    # ``bw_hz`` with coding rate 4/(4+coding_rate), instead of the flat
    # phy_rate_bps division. toa=False keeps the flat accounting (and the
    # committed byte/airtime baselines) unchanged.
    toa: bool = False
    sf: int = 7                     # LoRa spreading factor (7..12)
    bw_hz: float = 125_000.0        # LoRa channel bandwidth
    coding_rate: int = 1            # CR index: 1..4 -> 4/5..4/8
    preamble_syms: int = 8
    # per-round airtime budget: duty_cycle × round_period_s seconds of
    # airtime (plus ARQ backoff waits) per node per round; 0 period = no
    # budget (∞). Frames that exhaust the budget are abandoned and their
    # mass falls back to the CHOCO residual via error feedback.
    duty_cycle: float = 1.0
    round_period_s: float = 0.0
    # CHOCO error feedback: update the control sequence v with the
    # *delivered* delta only, so lost frames stay in the next residual
    error_feedback: bool = True
    seed: int = 0                   # SNR shadowing draw seed

    def replace(self, **kw) -> "TransportConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ParticipationConfig:
    """Barrier-free round model (DESIGN.md §12): which nodes show up.

    A node that does not participate in a round performs no local steps,
    transmits nothing, and integrates nothing — its params/v/v̄ freeze
    and the Metropolis-Hastings mixing row of every neighbor renormalizes
    over the delivered neighbor set (the missing weight folds into the
    self-loop, so the realized Ω stays doubly stochastic). Pure data;
    ``repro.core.gossip.ParticipationSchedule`` interprets it.
    """
    # iid per-round straggler skips: each subject node misses a round
    # with this probability (PRNG-pure from the round key)
    straggler_prob: float = 0.0
    # nodes subject to straggling; empty = every node
    stragglers: Tuple[int, ...] = ()
    # deterministic death/rejoin timelines: (node, die_round, rejoin_round)
    # — the node is out for die_round <= t < rejoin_round; rejoin < 0
    # means it never comes back
    dead: Tuple[Tuple[int, int, int], ...] = ()

    @property
    def active(self) -> bool:
        return self.straggler_prob > 0.0 or len(self.dead) > 0

    def replace(self, **kw) -> "ParticipationConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ContinualConfig:
    """Streaming drift + continual posterior refresh (DESIGN.md §15).

    Pure data, mirroring :class:`TransportConfig` — the drift half is
    interpreted by ``repro.data.scenarios.DriftSchedule`` (severity
    trajectories pure in ``(seed, round)``), the refresh half by
    ``repro.core.posterior.DeviceSampleBank`` bank aging (window
    eviction + age-discounted BMA weights). ``FedTrainer(continual=...)``
    and ``launch/train.py --drift/--refresh-*`` consume it.
    """
    # -- drift schedule over the node-local training distribution --------
    scenario: str = "clean"       # shift family (repro.data.scenarios);
                                  # "clean" = no drift, bitwise-unchanged
    schedule: str = "step"        # constant | step | ramp | cyclic | piecewise
    severity: float = 0.0         # plateau / peak severity in [0, 1]
    base_severity: float = 0.0    # pre-onset severity (keeps caller shards)
    onset: int = 0                # first drifted round
    ramp_rounds: int = 0          # ramp duration (0 degenerates to step)
    period: int = 0               # cyclic period in rounds
    breakpoints: Tuple[Tuple[int, float], ...] = ()   # piecewise knots
    refresh_every: int = 1        # rounds per drift phase (pool re-draw)
    drift_seed: int = 0           # drift-synthesis stream seed
    # -- continual posterior refresh (bank aging) ------------------------
    # >0: posterior samples older than this many rounds are evicted from
    # the BMA (their weight masks to zero) — the moving-window posterior
    window: int = 0
    # <1: BMA weight decay**age (age in rounds since admission),
    # renormalized over the surviving window — newest samples dominate
    decay: float = 1.0

    @property
    def drifts(self) -> bool:
        return self.scenario not in ("", "clean")

    @property
    def ages(self) -> bool:
        return self.window > 0 or self.decay < 1.0

    def replace(self, **kw) -> "ContinualConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ServeConfig:
    """Uncertainty-aware serving plane (DESIGN.md §14).

    Pure data, mirroring :class:`TransportConfig` / :class:`ParticipationConfig`
    — ``repro.serve.engine`` interprets it and ``launch/serve.py`` is a thin
    argparse shim over it. The slot table is the fixed compiled shape:
    requests are admitted into / retired from ``slots`` lanes per engine
    step with zero recompiles after warmup.
    """
    slots: int = 8                  # fixed-shape request slot table size
    max_len: int = 128              # decode KV-cache capacity per slot
    max_new_tokens: int = 16        # decode generation budget per request
    temperature: float = 1.0        # decode softmax temperature
    # entropy-gated selective prediction: abstain (route-to-human) when the
    # predictive entropy exceeds this many nats; inf = always answer. The
    # rule is shared with the eval engine's selective accounting, so a
    # threshold tuned on an EvalReport transfers to serving unchanged.
    entropy_threshold: float = float("inf")
    # >0: the serving CLI polls the checkpoint dir at this period and
    # hot-swaps newly landed posterior banks into the running engine
    hot_swap_poll_s: float = 0.0
    # mesh axis name to shard the bank's sample axis over ("" = replicated);
    # BMA then scales with devices (core.posterior.place_ensemble)
    ensemble_axis: str = ""

    def replace(self, **kw) -> "ServeConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class FedConfig:
    """The one config for a federated run; pure data — a training run is a deterministic function of ``(FedConfig, seed)`` (DESIGN.md §1)."""
    num_nodes: int = 10             # K
    topology: str = "full"          # legacy string: full | ring | grid | star
    # full graph spec; when set it overrides the ``topology`` string
    topology_cfg: Optional[TopologyConfig] = None
    mixing: str = "metropolis"      # metropolis | max_degree | uniform
    local_steps: int = 8            # L (paper sweet spot)
    zeta: float = 0.03              # consensus mixing weight
    eta: float = 1e-4               # SGLD learning rate
    temperature: float = 1.0        # posterior tempering (1.0 = paper)
    burn_in: int = 700              # T_b
    rounds: int = 800               # T
    # compression
    # codec pipeline DSL: stages from identity | topk | block_topk | randk |
    # qsgd | sign, chained with "|", e.g. "block_topk|qsgd" (sparsify, then
    # quantize the survivors). See core/compression.py.
    compressor: str = "block_topk"
    compress_ratio: float = 0.01    # paper: 1% of parameters
    qsgd_levels: int = 16
    block_size: int = 1024          # block-local top-k granularity
    min_dense_size: int = 0         # leaves smaller than this sent dense
    # fused compress-in-update (DESIGN.md §13): encode Q(θ − v) straight
    # from (θ, v) in Pallas so the dense residual never hits HBM. False
    # keeps the two-pass materialize-then-encode path (bitwise reference).
    fused_compress: bool = False
    # per-layer pipeline overrides: (path_substring, pipeline_spec) pairs,
    # first match wins, "*" matches everything (à la sharding_hints.py).
    # e.g. (("embed", "block_topk"), ("*", "block_topk|qsgd")).
    layer_pipelines: Tuple[Tuple[str, str], ...] = ()
    algorithm: str = "cdbfl"        # cdbfl | dsgld | cffl | sgld
    control_dtype: str = "float32"  # v / v̄ storage (bfloat16 halves fed state)
    # lossy D2D frame transport (None = ideal links, today's teleport path)
    transport: Optional[TransportConfig] = None
    # barrier-free participation (None = every node, every round — the
    # global-barrier model, bitwise unchanged)
    participation: Optional[ParticipationConfig] = None
    # streaming drift + continual posterior refresh (None = static data
    # and the un-aged uniform-BMA bank, bitwise unchanged)
    continual: Optional[ContinualConfig] = None
    seed: int = 0


@dataclass(frozen=True)
class TrainConfig:
    """Frozen pure data — optimizer/schedule scalars only."""
    global_batch: int = 256
    seq_len: int = 4096
    steps: int = 100
    log_every: int = 10
    optimizer: str = "sgld"         # sgld | sgd | adamw
    lr: float = 1e-4
    weight_decay: float = 0.0
    grad_clip: float = 0.0
    warmup_steps: int = 0
    param_dtype: Any = "float32"


@dataclass(frozen=True)
class MeshConfig:
    """Frozen pure data naming mesh axes; deterministic mesh construction."""
    multi_pod: bool = False
    fed_axis: str = "data"          # mesh axis that carries federated nodes
    fsdp_axis: str = "data"         # axis params are fully-sharded over
    model_axis: str = "model"


# --------------------------------------------------------------------------
# Input shapes (assigned)
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    """Frozen pure data — static shapes, safe jit cache-key material."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode


INPUT_SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# --------------------------------------------------------------------------
# Architecture registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ArchSpec:
    """Frozen registry entry: full + reduced (``--trim``) configs for one arch id; pure data."""
    arch_id: str
    config: ModelConfig
    reduced: ModelConfig            # smoke-test variant (<=2 layers, d_model<=512)
    source: str                     # citation from the assignment table
    notes: str = ""
    # shapes this arch skips (with reason), e.g. {"long_500k": "full attention"}
    skips: Dict[str, str] = field(default_factory=dict)


_ARCHS: Dict[str, ArchSpec] = {}


def register_arch(spec: ArchSpec) -> ArchSpec:
    _ARCHS[spec.arch_id] = spec
    return spec


def get_arch(arch_id: str) -> ArchSpec:
    _ensure_configs_imported()
    if arch_id not in _ARCHS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCHS)}")
    return _ARCHS[arch_id]


def list_archs():
    _ensure_configs_imported()
    return sorted(_ARCHS)


def _ensure_configs_imported():
    # configs register themselves on import
    import repro.configs  # noqa: F401
