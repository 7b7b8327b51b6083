"""Analytic roofline terms per (arch x shape x mesh).

Why analytic: XLA's ``cost_analysis()`` counts the body of every
``while`` (scan-over-layers, chunked attention, SSM chunk scans) exactly
once, so HLO FLOPs under-report any deep/scanned model by up to the trip
count.  The roofline compute/communication terms are therefore derived in
closed form from the model equations (which this framework controls
end-to-end), with the dry-run's HLO numbers kept as a structural
cross-check (collective op inventory, memory analysis, partitioning
proof).  This mirrors production MFU accounting (e.g. 6ND + attention
term), extended with explicit bytes/collective models per parallelism
axis.

All quantities are **per device per step** unless suffixed _global.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax.numpy as jnp

from repro.models.config import ModelConfig, ShapeConfig
from repro.models.model import build_model

# The chip these rooflines model: a v5e pod, whatever device this
# process runs on.  Its constants are the kernel tuner's entry for that
# device kind (repro.kernels.autotune.CHIPS), so the stage-level roofline
# and the trace-time tile sweep never disagree about the hardware.
# VPU_OPS because min-plus semiring ops run on the VPU, NOT the MXU
# (no tropical matmul in silicon).
from repro.kernels.autotune import CHIPS  # noqa: E402

TARGET_CHIP = "TPU v5 lite"
PEAK_FLOPS = CHIPS[TARGET_CHIP].peak_flops
VPU_OPS = CHIPS[TARGET_CHIP].vpu_ops
HBM_BW = CHIPS[TARGET_CHIP].hbm_bw
# B/s per mesh axis (2 links per torus axis)
ICI_BW = 2 * CHIPS[TARGET_CHIP].ici_bw


@dataclasses.dataclass
class Roofline:
    flops: float                  # per device
    hbm_bytes: float              # per device
    coll_bytes_model: float       # over the "model" axis (intra-pod ICI)
    coll_bytes_data: float        # over the "data" axis (intra-pod ICI)
    coll_bytes_pod: float         # over the "pod" axis (inter-pod)
    model_flops_global: float     # 6*N_active*D reference
    notes: dict[str, float]

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def collective_s(self) -> float:
        # axes are independent tori; serialized worst-case = sum
        return (
            self.coll_bytes_model + self.coll_bytes_data
        ) / ICI_BW + self.coll_bytes_pod / (ICI_BW / 4)  # DCI slower

    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    def step_time_s(self) -> float:
        """No-overlap upper bound."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """Achievable-compute fraction: compute term / bound step time."""
        t = self.step_time_s()
        return self.compute_s / t if t else 0.0


def analyze_isomap(stage: str, *, n: int = 2**19, b: int = 4096,
                   d_feat: int = 784, d_out: int = 3,
                   multi_pod: bool = False, power_iters: int = 30,
                   knn_gather_features: bool = False) -> Roofline:
    """Analytic roofline for the paper's pipeline stages at production
    scale.  Key TPU-specific fact: the min-plus semiring has no MXU
    mapping, so APSP compute is charged against the VPU rate (VPU_OPS) by
    scaling the flops up by PEAK_FLOPS/VPU_OPS - the roofline then reads
    in 'MXU-equivalent seconds' like every other cell."""
    chips = 512 if multi_pod else 256
    pd = 32 if multi_pod else 16      # rows fold over ("pod","data")
    pm = 16
    nr, nc = n // pd, n // pm
    local = n // pd
    q = n // b
    vpu_scale = PEAK_FLOPS / VPU_OPS

    if stage == "knn":
        # ring: each device computes pd blocks of (local x local) partial
        # distances over its D/pm feature shard (MXU: -2XY^T dominates)
        if knn_gather_features:
            # one up-front feature all-gather + ring split over the freed
            # "model" axis (each rank walks pd/pm steps); blocks are
            # communication-free and compute stays balanced
            flops = (pd / pm) * (2.0 * local * local * d_feat)
            coll_model = local * d_feat * 4               # the one gather
            coll_data = (pd / pm) * local * d_feat * 4    # full-feature ring
        else:
            flops = pd * (2.0 * local * local * (d_feat / pm))
            coll_model = pd * local * local * 4           # per-step block psum
            coll_data = pd * local * (d_feat / pm) * 4    # ring permute traffic
        hbm = pd * (2.0 * local * local * 4)          # block write + top-k read
        return Roofline(
            flops=flops, hbm_bytes=hbm,
            coll_bytes_model=coll_model, coll_bytes_data=coll_data,
            coll_bytes_pod=0.0,
            model_flops_global=2.0 * n * n * d_feat,
            notes={"stage": 1.0},
        )
    if stage == "apsp":
        # q iterations: rank-b min-plus update of the local tile (VPU) +
        # replicated b^3 FW + 2 panel products
        ops_tile = q * 2.0 * nr * nc * b
        ops_fw = q * 2.0 * b * b * b              # replicated phase 1
        ops_panels = q * 2.0 * (b * b * nc + nr * b * b)
        flops = (ops_tile + ops_fw + ops_panels) * vpu_scale
        hbm = q * (2.0 * nr * nc * 4 + 2 * (b * nc + nr * b) * 4)
        coll_model = q * (nr * b * 4 + b * b * 4) * 2   # col panel + diag psum
        coll_data = q * (b * nc * 4) * 2                # row panel psum
        return Roofline(
            flops=flops, hbm_bytes=hbm,
            coll_bytes_model=coll_model, coll_bytes_data=coll_data,
            coll_bytes_pod=0.0,
            model_flops_global=2.0 * float(n) ** 3,
            notes={"vpu_scale": vpu_scale, "q": q},
        )
    if stage == "center":
        flops = 4.0 * nr * nc
        hbm = 2.0 * nr * nc * 4
        return Roofline(
            flops=flops, hbm_bytes=hbm,
            coll_bytes_model=nr * 4, coll_bytes_data=nc * 4,
            coll_bytes_pod=0.0,
            model_flops_global=4.0 * n * n,
            notes={},
        )
    # power iteration: it x (tile matvec + QR replicated)
    it = power_iters
    flops = it * (2.0 * nr * nc * d_out + 2.0 * n * d_out * d_out)
    hbm = it * (nr * nc * 4)
    coll_model = it * nr * d_out * 4 * 2
    coll_data = it * n * d_out * 4
    return Roofline(
        flops=flops, hbm_bytes=hbm,
        coll_bytes_model=coll_model, coll_bytes_data=coll_data,
        coll_bytes_pod=0.0,
        model_flops_global=it * 2.0 * n * n * d_out,
        notes={"iters": float(it)},
    )


def _param_counts(cfg: ModelConfig) -> dict:
    """Parameter byte/count groups needed by the comm model."""
    model = build_model(cfg)
    import numpy as np
    import jax
    from repro.sharding import ParamSpec

    def count(tree):
        return sum(
            int(np.prod(s.shape))
            for s in jax.tree.leaves(
                tree, is_leaf=lambda x: isinstance(x, ParamSpec)
            )
        )

    specs = model.param_specs()
    total = count(specs)
    embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    if cfg.tie_embeddings:
        embed = cfg.vocab * cfg.d_model
    return {"total": total, "embed_like": embed, "body": total - embed}


def _mixer_flops_per_layer(cfg: ModelConfig, b: int, s: int, kind: str,
                           kv_s: int | None = None) -> float:
    """Fwd FLOPs of the *non-parametric* part of one sequence-mixer layer
    (the parametric matmuls are covered by 2*N_active*T)."""
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h = cfg.n_heads
    if kind == "attn":
        kv = kv_s if kv_s is not None else s
        causal = 0.5 if kv_s is None else 1.0
        return 4.0 * b * s * kv * h * hd * causal      # QK^T + PV
    if kind == "mamba":
        di = cfg.mamba.inner(d)
        n = cfg.mamba.d_state
        return 10.0 * b * s * di * n                   # scan + C-contract
    if kind == "mlstm":
        kv = kv_s if kv_s is not None else s
        return 4.0 * b * s * kv * h * hd * 0.5 + 6.0 * b * s * h * kv
    if kind == "slstm":
        return 12.0 * b * s * d
    return 0.0


def analyze(cfg: ModelConfig, shape: ShapeConfig, *, multi_pod: bool,
            profile: str = "tp") -> Roofline:
    """profile: "tp" (default rules), "dp" (no tensor parallelism - model
    axis carries batch; for small models), "serve" (weights resident, no
    FSDP; decode).  Mirrors sharding.logical.PROFILES."""
    model = build_model(cfg)
    chips = 512 if multi_pod else 256
    pd, pm, pp = 16, 16, (2 if multi_pod else 1)
    b, s = shape.global_batch, shape.seq_len
    n_active = model.active_params()
    pc = _param_counts(cfg)
    psize = 2 if cfg.param_dtype != jnp.float32 else 4

    if shape.step == "train":
        tokens = b * s
        fwd_param = 2.0 * n_active * tokens
        mixer = sum(
            _mixer_flops_per_layer(cfg, b, s, pat.mixer)
            for pat in cfg.pattern
        ) * cfg.periods
        if cfg.kind == "encdec":
            # encoder self-attn + decoder cross-attn
            mixer += 4.0 * b * cfg.enc_seq**2 * cfg.d_model * cfg.enc_layers
            mixer += 4.0 * b * s * cfg.enc_seq * cfg.d_model * cfg.n_layers
        fwd = fwd_param + mixer
        # bwd 2x fwd; full-layer remat adds ~1x fwd of the layer stack
        remat = fwd if cfg.remat else 0.0
        flops_global = 3.0 * fwd + remat
        moe_pad = 0.0
        if cfg.moe:
            # capacity padding computes capacity_factor x the routed flops
            routed_frac = 0.55  # approx share of expert matmuls in N_active
            moe_pad = (cfg.moe.capacity_factor - 1.0) * routed_frac * flops_global
        flops = (flops_global + moe_pad) / chips

        # HBM: params+grads+opt touched once per step (f32) + activation
        # traffic ~ (reads+writes) of layer I/O with remat
        param_traffic = pc["total"] * 4 * 5 / chips     # p r/w, g, m r/w, v r/w amortized
        act_traffic = 12.0 * tokens * cfg.d_model * 2 * cfg.n_layers / chips
        hbm = param_traffic + act_traffic + flops / PEAK_FLOPS * 0  # dominated

        # collectives:
        t_local = tokens / (pd * pp)
        if profile == "dp":
            # no TP: the model axis is a DP axis; its cost is one grad
            # all-reduce of the (data-axis-sharded) parameters
            coll_model = 2 * pc["total"] * 4 / pd
        else:
            #  model axis: 2 psums/layer fwd (+2 bwd) of (T_local, d) bf16
            coll_model = 4 * cfg.n_layers * t_local * cfg.d_model * 2 * 2
        #  data axis: FSDP all-gather params fwd+bwd(remat) + grad RS
        fsdp_bytes = pc["body"] * 4 / pm               # per model-shard
        coll_data = (2 + (1 if cfg.remat else 0)) * fsdp_bytes + 2 * fsdp_bytes
        #  pod axis: DP grad all-reduce of the pod-replicated shard
        coll_pod = 2 * pc["total"] * 4 / (pd * pm) if multi_pod else 0.0
        return Roofline(
            flops=flops,
            hbm_bytes=hbm,
            coll_bytes_model=coll_model,
            coll_bytes_data=coll_data,
            coll_bytes_pod=coll_pod,
            model_flops_global=6.0 * n_active * tokens,
            notes={"fwd_param": fwd_param, "mixer": mixer, "moe_pad": moe_pad},
        )

    if shape.step == "prefill":
        tokens = b * s
        fwd_param = 2.0 * n_active * tokens
        mixer = sum(
            _mixer_flops_per_layer(cfg, b, s, pat.mixer)
            for pat in cfg.pattern
        ) * cfg.periods
        if cfg.kind == "encdec":
            mixer += 4.0 * b * cfg.enc_seq**2 * cfg.d_model * cfg.enc_layers
            mixer += 4.0 * b * s * cfg.enc_seq * cfg.d_model * cfg.n_layers
        flops = (fwd_param + mixer) / chips
        param_bytes = pc["total"] * 4 / chips
        act = 8.0 * tokens * cfg.d_model * 2 * cfg.n_layers / chips
        cache = _cache_bytes(cfg, b, s) / chips
        t_local = tokens / (pd * pp)
        coll_model = 2 * cfg.n_layers * t_local * cfg.d_model * 2 * 2
        coll_data = 2 * pc["body"] * 4 / pm
        return Roofline(
            flops=flops,
            hbm_bytes=param_bytes + act + cache,
            coll_bytes_model=coll_model,
            coll_bytes_data=coll_data,
            coll_bytes_pod=0.0,
            model_flops_global=2.0 * n_active * tokens,
            notes={"cache_bytes": cache},
        )

    # decode: one token per sequence; params + cache reads dominate
    tokens = b * 1
    fwd_param = 2.0 * n_active * tokens
    mixer = sum(
        _mixer_flops_per_layer(cfg, b, 1, pat.mixer, kv_s=s)
        for pat in cfg.pattern
    ) * cfg.periods
    flops = (fwd_param + mixer) / chips
    if profile == "serve":
        # weights resident: TP-sharded over "model" only, read every step
        param_bytes = pc["total"] * psize / pm
    else:
        param_bytes = pc["total"] * 4 / chips
    cache = _cache_bytes(cfg, b, s) / chips
    coll_model = 2 * cfg.n_layers * b * cfg.d_model * 2 * 2
    # decode attention over seq-sharded cache: per-layer psum of
    # (B, H, 1) stats + (B, H, hd) partials
    n_attn = sum(1 for p_ in cfg.pattern if p_.mixer == "attn") * cfg.periods
    coll_model += n_attn * b * cfg.n_heads * (cfg.resolved_head_dim + 2) * 4 * 2
    # baseline: FSDP weight all-gather every decode step; "serve" keeps
    # weights resident (the SPerf cell-B fix)
    coll_data = 0.0 if profile == "serve" else 2 * pc["body"] * 4 / pm
    return Roofline(
        flops=flops,
        hbm_bytes=param_bytes + cache,
        coll_bytes_model=coll_model,
        coll_bytes_data=coll_data,
        coll_bytes_pod=0.0,
        model_flops_global=6.0 * n_active * tokens,
        notes={"cache_bytes": cache},
    )


def _cache_bytes(cfg: ModelConfig, b: int, s: int) -> float:
    total = 0.0
    for pat in cfg.pattern:
        if pat.mixer == "attn":
            total += 2 * b * s * cfg.kv_heads * cfg.resolved_head_dim * 2
        elif pat.mixer == "mamba":
            di = cfg.mamba.inner(cfg.d_model)
            total += b * di * (cfg.mamba.d_state * 4 + (cfg.mamba.d_conv - 1) * 2)
        elif pat.mixer == "mlstm":
            hd = cfg.d_model // cfg.n_heads
            total += b * cfg.n_heads * (hd * hd + hd + 1) * 4
        else:
            total += 4 * b * cfg.d_model * 4
    total *= cfg.periods
    if cfg.kind == "encdec":
        total += 2 * b * cfg.enc_seq * cfg.kv_heads * cfg.resolved_head_dim * 2 * cfg.n_layers
    return total
