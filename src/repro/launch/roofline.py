"""Roofline-term derivation from compiled dry-run artifacts.

Terms (seconds), per (arch x shape x mesh) cell on TPU v5e.

IMPORTANT semantics (measured against a calibration program): the compiled
module is the per-device SPMD program, so ``cost_analysis()`` FLOPs/bytes
and the HLO collective shapes are all PER-DEVICE quantities:

    compute    = HLO_FLOPs_dev / 197e12          [bf16 peak / chip]
    memory     = HLO_bytes_dev / 819e9           [HBM bw / chip]
    collective = collective_bytes_dev / (2 * 50e9) [ICI links / chip]

collective_bytes is parsed from the compiled HLO text: the summed
result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (+ their async -start forms) — a
documented proxy for per-device on-wire volume.  Scan bodies are counted
once by XLA's analysis, so the dry-run lowers models with UNROLLED layer
loops (Model(scan_layers=False)).
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from ..chips import chip

_V5E = chip("TPU v5 lite")   # the dry-run's target chip
PEAK_FLOPS = _V5E.peak_flops     # bf16 / chip
HBM_BW = _V5E.hbm_bytes_per_s    # B/s / chip
ICI_BW_PER_LINK = 50e9       # B/s
ICI_LINKS = 2                # effective links engaged per chip (conservative)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective op kind."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        for op in COLLECTIVE_OPS:
            # match " all-gather(" / " all-gather-start(" as the op token
            if f" {op}(" in line or f" {op}-start(" in line:
                lhs = line.split(f" {op}", 1)[0]
                for dtype, dims in _SHAPE_RE.findall(lhs):
                    if dtype in _DTYPE_BYTES:
                        out[op] += _shape_bytes(dtype, dims)
                break
    return out


def roofline_terms(
    flops_dev: float,
    hbm_bytes_dev: float,
    collective_bytes_dev: float,
    chips: int,
) -> Dict[str, float]:
    """All inputs are per-device quantities (see module docstring)."""
    compute = flops_dev / PEAK_FLOPS
    memory = hbm_bytes_dev / HBM_BW
    collective = collective_bytes_dev / (ICI_LINKS * ICI_BW_PER_LINK)
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    terms["bottleneck"] = max(terms, key=lambda k: terms[k])[:-2]
    terms["step_s_lower_bound"] = max(compute, memory, collective)
    return terms


def active_param_count(cfg) -> int:
    """Active params for 6*N_active*D MoE model-FLOPs accounting."""
    total = cfg.param_count()
    if cfg.family != "moe" or cfg.n_experts == 0:
        return total
    ffe = 3 * cfg.d_model * cfg.d_ff_expert
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    routed_total = moe_layers * cfg.n_experts * ffe
    routed_active = moe_layers * cfg.top_k * ffe
    return total - routed_total + routed_active


def model_flops(cfg, shape_kind: str, tokens: int) -> float:
    """6*N*D train / 2*N*D inference forward (MoE: N_active)."""
    n = active_param_count(cfg)
    return (6.0 if shape_kind == "train" else 2.0) * n * tokens


def mfu_fraction(model_fl: float, seconds: float, chips: int) -> float:
    if seconds <= 0:
        return 0.0
    return model_fl / (seconds * chips * PEAK_FLOPS)


def analytic_attention_flops(cfg, B: int, Tq: int, Tk: int,
                             windows=None, decode: bool = False) -> float:
    """Global attention FLOPs (scores + PV) across all layers.

    XLA counts a scan body once, and Pallas kernels appear as custom calls
    with no cost, so attention FLOPs are accounted analytically:
        2 * 2 * B * Hq * Tq * Tk_eff * dh   per attention layer,
    with Tk_eff halved for causal self-attention over a fresh sequence and
    clipped to the window for sliding-window layers.  Backward (train)
    multiplies by 3 at the call site via model_flops conventions.
    """
    fam = cfg.family
    if fam == "ssm":
        return 0.0

    def layer_flops(win, tq, tk, hq, dh, causal_fresh):
        tk_eff = tk
        if win and win > 0:
            tk_eff = min(tk, win)
        elif causal_fresh:
            tk_eff = tk / 2.0
        return 4.0 * B * hq * tq * tk_eff * dh

    if fam == "hybrid":
        n_attn = (cfg.n_layers // cfg.shared_attn_period)
        hq, dh = cfg.n_heads, cfg.head_dim
        return n_attn * layer_flops(0, Tq, Tk, hq, dh, not decode)
    if fam == "audio":
        hq, dh = cfg.n_heads, cfg.head_dim
        enc = cfg.n_enc_layers * layer_flops(0, Tk, Tk, hq, dh, False)
        if decode:
            enc = 0.0
        dec_self = cfg.n_dec_layers * layer_flops(0, Tq, Tk, hq, dh,
                                                  not decode)
        dec_cross = cfg.n_dec_layers * layer_flops(0, Tq, Tk, hq, dh, False)
        return enc + dec_self + dec_cross
    if cfg.mla:
        hq = cfg.n_heads
        dh = cfg.qk_nope_dim + cfg.qk_rope_dim + cfg.v_head_dim
        return cfg.n_layers * layer_flops(0, Tq, Tk, hq, dh / 2 * 2,
                                          not decode)
    hq, dh = cfg.n_heads, cfg.head_dim
    total = 0.0
    for i in range(cfg.n_layers):
        win = cfg.window if (cfg.window and not cfg.layer_is_global(i)) \
            else (cfg.window if cfg.window and not cfg.local_global_period
                  else 0)
        total += layer_flops(win, Tq, Tk, hq, dh, not decode)
    return total


def analytic_memory_estimate(cfg, kind: str, B: int, S: int,
                             axes: dict, fsdp: bool,
                             cache_bytes_dev: float = 0.0,
                             seq_shard: bool = False) -> dict:
    """Per-device HBM estimate for the TPU target (bytes).

    The XLA-CPU backend has no memory-aware scheduling, so its
    memory_analysis() keeps one recomputed attention buffer alive per layer
    (measured: temp grows ~1.8 GB/layer on CPU, constant on TPU-style
    schedules).  This analytic model is the "fits on v5e" evidence and is
    reported next to the raw CPU numbers:

      params(bf16/TP)  + ZeRO-1 moments(fp32/TPxDP) + grads(bf16/TP)
      + layer-input residuals (remat) + a bounded transient working set
      + (serving) exact sharded cache bytes.
    """
    n = cfg.param_count()
    tp = axes.get("model", 1)
    dp = axes.get("data", 1) * axes.get("pod", 1)
    params_dev = 2.0 * n / tp / (dp if fsdp else 1)
    d = cfg.d_model
    b_dev = max(1, B // dp)
    out = {"params_bytes": params_dev}
    if kind == "train":
        out["moments_bytes"] = 8.0 * n / tp / axes.get("data", 1)
        out["grads_bytes"] = 2.0 * n / tp / (dp if fsdp else 1)
        layers = cfg.n_layers
        res = layers * b_dev * S * d * 2.0
        if seq_shard:
            res /= tp  # sequence-sharded residual stream
        out["residual_bytes"] = res
        # transient: few activation-sized f32 buffers + one attention chunk;
        # sequence sharding also shards the transients outside the gathered
        # attention/mlp interiors
        hq = max(1, cfg.n_heads)
        trans = (8.0 * b_dev * S * d * 4.0
                 + 2.0 * b_dev * max(1, hq // tp) * S * 512 * 4.0)
        if seq_shard:
            trans = trans / tp + 2.0 * b_dev * S * d * 4.0 / max(tp // 4, 1)
        out["transient_bytes"] = trans
    else:
        out["cache_bytes"] = cache_bytes_dev
        out["transient_bytes"] = 8.0 * b_dev * max(S if kind == "prefill"
                                                   else 1, 1) * d * 4.0
    out["total_bytes"] = float(sum(out.values()))
    out["fits_16gb_v5e"] = bool(out["total_bytes"] < _V5E.hbm_bytes)
    return out


_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{(\{[0-9,{} ]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\](T\(\d+,\d+\))?<=\[(\d+)\]"
)


def _line_crosses_pods(line: str, pod_size: int) -> bool:
    """Does this collective's replica grouping span pod boundaries?

    Handles explicit ``replica_groups={{0,256},{1,257},...}`` and iota
    forms ``replica_groups=[G,N]<=[512]`` (contiguous groups of N) /
    ``[G,N]T(1,0)<=[512]`` (strided groups)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        g, n, transpose, total = (int(m.group(1)), int(m.group(2)),
                                  m.group(3), int(m.group(4)))
        if total <= pod_size:
            return False
        if transpose:
            # groups pick every (total//n)-th device: stride g
            return (n - 1) * g >= pod_size
        return n > pod_size
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        for grp in m.group(1).split("},"):
            ids = [int(x) for x in re.findall(r"\d+", grp)]
            if ids and (max(ids) // pod_size) != (min(ids) // pod_size):
                return True
        return False
    return False  # no groups -> all devices; caller decides


def dci_bytes_from_hlo(hlo_text: str, pod_size: int = 256) -> Dict[str, int]:
    """Split per-device collective bytes into intra-pod (ICI) vs
    pod-crossing (DCI) by replica-group analysis — the TPU analogue of the
    paper's intra- vs inter-region byte accounting."""
    out = {"ici": 0, "dci": 0}
    for line in hlo_text.splitlines():
        for op in COLLECTIVE_OPS:
            if f" {op}(" in line or f" {op}-start(" in line:
                lhs = line.split(f" {op}", 1)[0]
                nbytes = 0
                for dtype, dims in _SHAPE_RE.findall(lhs):
                    if dtype in _DTYPE_BYTES:
                        nbytes += _shape_bytes(dtype, dims)
                crossing = _line_crosses_pods(line, pod_size) or (
                    "replica_groups" not in line
                )
                out["dci" if crossing else "ici"] += nbytes
                break
    return out


def dci_message_count_from_hlo(hlo_text: str, pod_size: int = 256) -> int:
    """Per-device count of pod-crossing peer messages (the paper's
    inter-region message count).  For an all-to-all over a group, each
    device sends one message to every OTHER-POD member of its group; for
    gather/reduce-style collectives a ring crosses the pod boundary twice.
    This is the alpha-term the 3-step aggregation minimizes — byte counts
    alone cannot distinguish flat from hierarchical transports."""
    total = 0
    for line in hlo_text.splitlines():
        op_kind = None
        for op in COLLECTIVE_OPS:
            if f" {op}(" in line or f" {op}-start(" in line:
                op_kind = op
                break
        if op_kind is None:
            continue
        other = 0
        m = _GROUPS_IOTA_RE.search(line)
        if m:
            g, n, transpose, tot = (int(m.group(1)), int(m.group(2)),
                                    m.group(3), int(m.group(4)))
            if tot > pod_size:
                if transpose and (n - 1) * g >= pod_size:
                    other = n // 2
                elif not transpose and n > pod_size:
                    other = n // 2
        else:
            m = _GROUPS_EXPL_RE.search(line)
            if m:
                first = re.findall(r"\d+", m.group(1).split("},")[0])
                ids = [int(x) for x in first]
                if ids:
                    pods = [i // pod_size for i in ids]
                    other = sum(1 for p in pods if p != pods[0])
        if other:
            total += other if op_kind == "all-to-all" else 2
    return total
