"""Roofline-term extraction from a compiled (dry-run) executable.

``compiled.cost_analysis()`` supplies per-device HLO FLOPs and bytes
accessed; collective traffic is NOT in cost_analysis, so we parse the
post-SPMD HLO text and sum the bytes every collective moves over ICI,
using ring-algorithm transfer factors per op kind:

    all-gather          out_bytes * (G-1)/G     (out = gathered result)
    reduce-scatter      out_bytes * (G-1)       (= operand * (G-1)/G)
    all-reduce          2 * bytes * (G-1)/G     (reduce-scatter + all-gather)
    all-to-all          bytes * (G-1)/G
    collective-permute  bytes

where G is the replica-group size parsed from the op's ``replica_groups``.
The raw sum of result bytes is reported too (``collective_raw_bytes``).

Hardware model: per-chip peaks keyed by ``jax.Device.device_kind``
(:data:`PEAKS`); a device missing from the table is an error, never a
default.  The dry-run targets TPU v5e (:data:`DRYRUN_TARGET`).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""

    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # bytes/s per ICI link (single-link serialization)
    source: str


#: Source for v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per
#: chip, taken as 4 links of 400 Gbit/s = 50 GB/s each.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}

#: the device_kind the dry-run compiles for (the production meshes)
DRYRUN_TARGET = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The :data:`PEAKS` row of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# `%name = <result-type> <op>(` where op may have a -start suffix (async).
_OP_RE = re.compile(
    r"=\s*(\(?[^()]*?\)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return m.group(1).count(",") + 1
    return default


@dataclass
class CollectiveStats:
    ici_bytes: float = 0.0         # ring-model bytes over ICI, per device
    raw_bytes: float = 0.0         # sum of collective result bytes
    counts: dict = field(default_factory=dict)
    by_kind_bytes: dict = field(default_factory=dict)

    def as_dict(self) -> dict:
        return {
            "ici_bytes": self.ici_bytes,
            "raw_bytes": self.raw_bytes,
            "counts": self.counts,
            "by_kind_bytes": self.by_kind_bytes,
        }


def collective_stats(hlo_text: str, n_devices: int) -> CollectiveStats:
    st = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        result_type, kind, suffix = m.group(1), m.group(2), m.group(3)
        size = _shape_bytes(result_type)
        G = max(_group_size(line, n_devices), 1)
        if kind == "all-gather":
            moved = size * (G - 1) / G
        elif kind == "reduce-scatter":
            moved = size * (G - 1)
        elif kind == "all-reduce":
            moved = 2.0 * size * (G - 1) / G
        elif kind == "all-to-all":
            moved = size * (G - 1) / G
        else:  # collective-permute
            moved = float(size)
        st.ici_bytes += moved
        st.raw_bytes += size
        st.counts[kind] = st.counts.get(kind, 0) + 1
        st.by_kind_bytes[kind] = st.by_kind_bytes.get(kind, 0.0) + moved
    return st


def memory_analysis_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for name in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, name, None)
        if v is not None:
            out[name] = int(v)
    return out


def cost_analysis_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items() if isinstance(v, (int, float))}


def roofline_terms(
    *, flops: float, bytes_accessed: float, ici_bytes: float,
    device_kind: str,
) -> dict:
    """Three per-device roofline terms (seconds) + the dominant one.

    ``flops``/``bytes_accessed`` come from the per-device (post-SPMD)
    module's cost_analysis; ``ici_bytes`` from :func:`collective_stats`;
    the peaks are those of ``device_kind`` (:func:`peaks`).
    """
    pk = peaks(device_kind)
    compute_s = flops / pk.flops
    memory_s = bytes_accessed / pk.hbm_bw
    collective_s = ici_bytes / pk.ici_bw
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dominant = max(terms, key=terms.get)
    terms["dominant"] = dominant.replace("_s", "")
    total = max(compute_s, memory_s, collective_s)
    terms["bound_s"] = total
    terms["compute_fraction_of_bound"] = compute_s / total if total else 0.0
    return terms


def model_flops(cfg, step_kind: str, global_batch: int, seq_len: int) -> float:
    """Useful-work estimate: 6·N_active·D (train) / 2·N_active·D (inference);
    D = tokens processed (decode: one token per sequence)."""
    n = cfg.active_param_count()
    mult = 6.0 if step_kind == "train" else 2.0
    tokens = global_batch * (seq_len if step_kind != "decode" else 1)
    return mult * n * tokens
