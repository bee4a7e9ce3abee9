"""Roofline analysis from compiled dry-run artifacts (deliverable (g)).

Implements BOTH performance models:
  * the classic compute roofline (paper Fig. 2) — MFU-oriented terms,
  * the paper's bandwidth roofline (Fig. 3) — MBU as a first-class metric
    for the sparse path (§1.4.2 Performance Modeling).

Terms (per (arch × shape × mesh), single-pod):
  compute_s    = HLO_FLOPs / (chips × peak FLOP/s)
  memory_s     = HLO_bytes / (chips × peak HBM bytes/s)
  collective_s = Σ collective operand bytes / (chips × peak ICI bytes/s)

with the peaks of a named target device (``CHIP_PEAKS``).

IMPORTANT accounting note (verified empirically): ``compiled.cost_analysis``
and the parsed HLO of an SPMD executable are **per device** — one chip's
program. The formulas above are therefore evaluated with per-chip numerators
over per-chip denominators, which is equivalent: HLO_FLOPs(total)/(chips ×
peak) == HLO_FLOPs(per-chip)/peak. ``Roofline`` takes the per-chip numbers
and ``chips`` only rescales MODEL_FLOPS (a global quantity) to per-chip.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peak rates of one accelerator kind."""

    flops: float       # bf16 FLOP/s
    hbm_bw: float      # HBM bytes/s
    hbm_bytes: int     # HBM capacity
    ici_bw: float      # chip-to-chip interconnect bytes/s, all links


# The one table of chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GiB
# HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9,
                             hbm_bytes=16 * 2**30, ici_bw=1600e9 / 8),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; an unknown device is an error, not a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(CHIP_PEAKS)})") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]+m[0-9]+(?:fn)?)?)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _group_size(rhs: str, default: int = 1) -> int:
    m = _GROUPS_RE.search(rhs)               # [n_groups, gsize]<=[...]
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(rhs)          # {{0,1,...,k-1},...}
    if m:
        return max(len(m.group(1).split(",")), 1)
    return default


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-chip *operand-equivalent* bytes of every collective, by kind.

    The optimized-HLO printer emits operands as bare names (`all-reduce(%x)`)
    with no inline type, so operand parsing silently under-counts (audited:
    26/49 collectives of an LM train step, including every ZeRO-1 weight
    all-gather, would count as 0). Instead we use the RESULT type — always
    printed — plus the replica group size g:

      all-reduce          operand == result            -> result
      all-to-all          operand == result            -> result
      collective-permute  operand == result            -> result
      all-gather          operand == result / g        -> result / g
      reduce-scatter      operand == result x g        -> result x g

    This keeps the assignment's "sum operand sizes" rule, printer-
    independent. (Ring wire-bytes would be ~2x for all-reduce and
    x(g-1)/g for ag/rs — a constant factor the §Roofline narrative notes.)
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+)$", s)
        if not m:
            continue
        rhs = m.group(1)
        kind, kmatch = None, None
        for k in _COLLECTIVES:
            kmatch = re.search(rf"\b{k}(-start|-done)?\(", rhs)
            if kmatch:
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done(" in rhs:
            continue  # counted at -start
        # result type(s) = everything before the OP NAME (handles tuple
        # results whose "(" precedes the op's operand paren)
        shapes = _SHAPE_RE.findall(rhs[: kmatch.start()])
        if not shapes:
            continue
        result = sum(_shape_bytes(d, dims) for d, dims in shapes)
        g = _group_size(rhs)
        if kind == "all-gather":
            nbytes = result // max(g, 1)
        elif kind == "reduce-scatter":
            nbytes = result * g
        else:
            nbytes = result
        out[kind] += nbytes
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def wire_bytes(hlo_text: str) -> dict[str, int]:
    """Ring-algorithm wire traffic per chip (the physical-link view):

      all-reduce         2*S*(g-1)/g      (reduce-scatter + all-gather ring)
      all-gather         S*(g-1)/g        (S = FULL gathered result)
      reduce-scatter     S_full*(g-1)/g   (S_full = result*g)
      all-to-all         S*(g-1)/g
      collective-permute S

    Reported alongside the assignment's operand rule in EXPERIMENTS.md; the
    two differ by bounded constants, so variant DELTAS agree in sign.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+)$", s)
        if not m:
            continue
        rhs = m.group(1)
        kind, kmatch = None, None
        for k in _COLLECTIVES:
            kmatch = re.search(rf"\b{k}(-start|-done)?\(", rhs)
            if kmatch:
                kind = k
                break
        if kind is None or f"{kind}-done(" in rhs:
            continue
        shapes = _SHAPE_RE.findall(rhs[: kmatch.start()])
        if not shapes:
            continue
        result = sum(_shape_bytes(d, dims) for d, dims in shapes)
        g = max(_group_size(rhs), 1)
        frac = (g - 1) / g
        if kind == "all-reduce":
            nbytes = int(2 * result * frac)
        elif kind == "all-gather":
            nbytes = int(result * frac)
        elif kind == "reduce-scatter":
            nbytes = int(result * g * frac)
        elif kind == "all-to-all":
            nbytes = int(result * frac)
        else:  # collective-permute
            nbytes = result
        out[kind] += nbytes
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                  # PER-CHIP HLO flops (SPMD executable)
    hbm_bytes: float              # PER-CHIP bytes accessed
    coll_bytes: float             # PER-CHIP collective operand bytes
    chips: int
    target: str                   # device_kind whose peaks bound the terms
    model_flops: float = 0.0      # GLOBAL 6·N·D style useful-work estimate

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.target)

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / self.peaks.ici_bw

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Roofline step-time lower bound (perfect overlap of the 3 engines)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / compiled FLOPs (both per-chip). <1 = remat/dispatch
        waste; >1 means the work is not FLOP-shaped (sparse/memory path)."""
        return (self.model_flops / self.chips) / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPS-at-peak time over the step lower bound: how close the
        compiled program could get to the hardware roofline if it ran at the
        bound of its dominant term. 1.0 = the useful work IS the bound."""
        if self.step_s == 0:
            return 0.0
        return (self.model_flops / self.chips / self.peaks.flops) / self.step_s

    @property
    def mbu_bound(self) -> float:
        """Paper's bandwidth-roofline view: fraction of step time that is
        HBM-bound (MBU target = memory_s / step_s)."""
        return self.memory_s / self.step_s if self.step_s else 0.0

    def to_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes, "chips": self.chips,
            "target": self.target,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "bound": self.bound,
            "step_s_lower_bound": self.step_s,
            "useful_flops_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


# ---------------------------------------------------------------------------
# MODEL_FLOPS estimates per family (useful work, not compiled work)
# ---------------------------------------------------------------------------

def model_flops(arch, shape) -> float:
    fam = arch.family
    if fam == "lm":
        cfg = arch.model
        n = cfg.param_count()
        t, b = shape["seq_len"], shape["global_batch"]
        hd = cfg.head_dim
        if shape.kind == "train":
            attn = 0.5 * 12 * cfg.n_layers * b * t * t * hd * cfg.n_heads  # causal fwd+bwd
            return 6.0 * n * b * t + attn
        if shape.kind == "prefill":
            attn = 0.5 * 4 * cfg.n_layers * b * t * t * hd * cfg.n_heads
            return 2.0 * n * b * t + attn
        # decode: one token against an S-long cache
        attn = 4.0 * cfg.n_layers * b * t * hd * cfg.n_heads
        return 2.0 * n * b + attn
    if fam == "recsys":
        b = shape.get("batch", 1)
        m = arch.model
        # per_ex is a forward pass at 2 FLOPs per MAC; training adds the
        # backward's two matmuls per forward one
        mults = {"train": 3.0, "serve": 1.0, "retrieval": 1.0}[shape.kind]
        per_ex = _recsys_dense_flops(arch.arch_id, m)
        if shape.kind == "retrieval":
            b = shape["n_candidates"]
        return mults * per_ex * b
    if fam == "gnn":
        m = arch.model
        d = m.d_hidden
        per_node = m.n_layers * 2 * (2 * d * d)       # two MLP layers per GIN layer
        if shape.kind == "full_graph":
            n, e = shape["n_nodes"], shape["n_edges"]
            agg = m.n_layers * e * d * 2
            return 3.0 * (per_node * n + agg + 2 * n * shape["d_feat"] * d)
        if shape.kind == "minibatch":
            n = shape["batch_nodes"] * 166
            e = shape["batch_nodes"] * 165
            return 3.0 * (per_node * n + m.n_layers * e * d * 2)
        n = shape["batch"] * shape["n_nodes"]
        e = shape["batch"] * shape["n_edges"]
        return 3.0 * (per_node * n + m.n_layers * e * d * 2)
    return 0.0


def _mlp_flops(dims) -> float:
    return sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))


def _recsys_dense_flops(arch_id: str, m) -> float:
    if arch_id == "dlrm-mlperf":
        f = m.n_sparse + 1
        inter = 2.0 * f * f * m.embed_dim
        return (_mlp_flops((m.n_dense,) + m.bot_mlp)
                + inter + _mlp_flops((m.bot_mlp[-1] + f * (f - 1) // 2,) + m.top_mlp))
    if arch_id == "wide-deep":
        return _mlp_flops((m.n_sparse * m.embed_dim,) + m.mlp + (1,)) + 2 * m.wide_dim
    if arch_id == "sasrec":
        d, t = m.embed_dim, m.seq_len
        per_block = 3 * 2 * t * d * d + 2 * 2 * t * t * d + 2 * 2 * t * d * d
        return m.n_blocks * per_block
    if arch_id == "mind":
        d, t, k = m.embed_dim, m.seq_len, m.n_interests
        return 2 * t * d * d + m.capsule_iters * (2 * k * t * d * 2) + 2 * d * d
    return 0.0


def flash_attention_cost(b_loc: int, t: int, h_loc: int, hk_loc: int, hd: int,
                         train: bool, q_chunk: int = 1024) -> dict:
    """Analytic per-device cost of the causal flash kernel for one layer.

    flops: QKᵀ + PV = 2 MACs × T²·hd per head, causal-halved; train adds
    bwd (2×) and remat re-forward (1×) → ×4 total.
    bytes: per q-chunk pass the kernel streams all of K,V once; Q and O
    stream once. Train ≈ ×3 (fwd + remat-fwd + bwd reads dO,Q,K,V writes
    dQ,dK,dV).
    """
    nq = max(t // q_chunk, 1)
    fwd_flops = 0.5 * 4.0 * b_loc * h_loc * t * t * hd
    fwd_bytes = b_loc * 2 * (nq * 2 * t * hk_loc * hd + 2 * t * h_loc * hd)
    mult_f = 4.0 if train else 1.0
    mult_b = 3.0 if train else 1.0
    return {"flops": mult_f * fwd_flops, "bytes": mult_b * fwd_bytes}
