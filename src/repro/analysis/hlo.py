"""Compiled-HLO text analysis (promoted from `repro.launch.hlo`).

One parser serves every consumer — the multi-pod dry-run harness
(`launch/dryrun.py`), the ring scale-out benchmark, the distributed byte
pins (`tests/test_dist_lowering.py`), and the `repro.analysis` rules —
so the collective-byte accounting cannot drift between them.

`analyze_hlo` walks the HLO text once and extracts:

  * collectives  — one record per collective instruction (kind, payload
                   bytes, replica groups / source-target pairs), with the
                   async `-start`/`-done` pair counted ONCE: `-done` lines
                   carry no shape of their own and are skipped, and a
                   `-start` op's tuple output drops the in-flight operand
                   echo and the rank-0 integer context slots (u32[]/s32[]
                   handles) so bytes reflect the payload, never the
                   bookkeeping.
  * scatter_ops  — count of compiled `scatter` instructions (the lowered
                   form of data-dependent `at[].add`/`at[].max`).
  * convert_ops  — (src_dtype -> dst_dtype) counts of `convert`
                   instructions (the dtype-policy rule's raw material).
  * input_output_alias — (output_index, parameter) pairs declared in the
                   module header, i.e. which donated arguments XLA
                   actually aliased (the donation rule's raw material).

`op_names_from_hlo` maps each instruction to the JAX name stack in its
metadata, which carries the program's `jax.named_scope`s (`edge.gather`,
`edge.reorder`, `edge.reduce`) and marks backward ops `transpose(...)`: a
profiler's device events name instructions, so the map attributes device
time to the program's scopes.

`collective_bytes_from_hlo` keeps its historical return shape
({bytes_per_kind, count_per_kind, total_bytes}) on top of the same walk.

Byte convention: a collective's payload is its OUTPUT shape (for
all-gather the gathered size, for reduce-scatter the scattered size) — a
consistent per-device proxy that the analytic formulas in
`repro.gnn.sync` (`sync_bytes_per_round` et al.) and the budget hook
(`collective_budget`) price identically.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

__all__ = [
    "CollectiveOp",
    "analyze_hlo",
    "collective_bytes_from_hlo",
    "input_output_aliases_from_hlo",
    "op_names_from_hlo",
]

_COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(?:\.\d+)?\s*\(",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

# the annotation is a brace list of brace lists — `{{0,1},{1,2}}`; a
# non-greedy `.*?` would stop at the FIRST inner `}` and truncate every
# multi-group annotation, so consume inner groups explicitly
_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=\{((?:\{[0-9,\s]*\}[,\s]*)*)\}")
_SOURCE_TARGET_RE = re.compile(
    r"source_target_pairs=\{((?:\{[0-9,\s]*\}[,\s]*)*)\}")
_GROUP_RE = re.compile(r"\{([0-9,\s]*)\}")

_CONVERT_RE = re.compile(r"=\s*([a-z0-9]+)\[[0-9,]*\][^=]*?"
                         r"\bconvert(?:\.\d+)?\s*\(\s*([a-z0-9]+)\[")
_SCATTER_RE = re.compile(r"=\s*[^=]*?\bscatter(?:\.\d+)?\s*\(")
_OP_NAME_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bop_name="([^"]*)"', re.M)


def _shape_entries(region: str) -> list:
    """[(dtype, nelems, rank0)] for every `dtype[dims]` in `region` (known
    dtypes only — `token[]` and opaque types carry no payload)."""
    out = []
    for dt, dims in _SHAPE_RE.findall(region):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for tok in dims.split(","):
            if tok:
                n *= int(tok)
        out.append((dt, n, not dims))
    return out


def _payload_entries(kind_suffix: Optional[str], outputs: list,
                     operands: list) -> list:
    """Reduce an op's output shape entries to its true payload.

    Plain (sync) collectives: the output IS the payload. `-start` forms
    return a tuple holding async bookkeeping alongside the result:
    rank-0 integer context slots (`u32[]`/`s32[]` handles) and one echo of
    each operand buffer (the in-flight source). Both are dropped — but
    never the last remaining entry, so a `-start` whose output equals its
    operand (all-reduce) still counts its single payload once.
    """
    if kind_suffix != "-start":
        return [(dt, n) for dt, n, _ in outputs]
    entries = [(dt, n) for dt, n, rank0 in outputs
               if not (rank0 and dt in ("u32", "s32", "u64", "s64")
                       and len(outputs) > 1)]
    for op_dt, op_n, _ in operands:
        if len(entries) <= 1:
            break
        try:
            entries.remove((op_dt, op_n))
        except ValueError:
            pass
    return entries


def _parse_groups(text: str) -> list:
    return [
        [int(t) for t in grp.split(",") if t.strip()]
        for grp in _GROUP_RE.findall(text)
    ]


@dataclasses.dataclass
class CollectiveOp:
    """One compiled collective instruction."""

    kind: str                  # all-reduce | all-gather | ... (no suffix)
    is_start: bool             # async -start form
    payload_bytes: int         # output-shape payload (bookkeeping removed)
    dtypes: tuple              # payload dtypes, e.g. ("s8", "f32")
    replica_groups: list       # [[0,1,2,3]] etc. ([] when absent)
    source_target_pairs: list  # collective-permute routing ([] when absent)

    @property
    def group_size(self) -> int:
        """Devices per replica group (0 when unannotated)."""
        if self.replica_groups:
            return max(len(g) for g in self.replica_groups)
        if self.source_target_pairs:
            return len({p for pair in self.source_target_pairs for p in pair})
        return 0


def analyze_hlo(hlo_text: str) -> dict:
    """One-pass structural summary of compiled HLO text (module docstring).

    Returns {"collectives": [CollectiveOp], "bytes_per_kind",
    "count_per_kind", "total_bytes", "scatter_ops", "convert_ops",
    "input_output_alias"}.
    """
    collectives: list[CollectiveOp] = []
    scatter_ops = 0
    convert_ops: dict[tuple, int] = {}

    for raw in hlo_text.splitlines():
        line = raw.strip()
        if "=" not in line:
            continue
        rhs = line.split("=", 1)[1]

        cm = _CONVERT_RE.search(line)
        if cm and " convert" in line:
            dst, src = cm.group(1), cm.group(2)
            convert_ops[(src, dst)] = convert_ops.get((src, dst), 0) + 1

        if (_SCATTER_RE.search(line) and "reduce-scatter" not in line
                and "select-and-scatter" not in line):
            scatter_ops += 1

        m = _COLLECTIVE_RE.search(rhs)
        if not m:
            continue
        kind, suffix = m.group(1), m.group(2)
        if suffix == "-done":
            # the paired -start already counted this transfer
            continue

        out_entries = _shape_entries(rhs[: m.start()])
        close = rhs.find(")", m.end())
        operand_region = rhs[m.end(): close if close >= 0 else len(rhs)]
        op_entries = _shape_entries(operand_region)
        payload = _payload_entries(suffix, out_entries, op_entries)
        attrs = rhs[close:] if close >= 0 else ""
        collectives.append(CollectiveOp(
            kind=kind,
            is_start=(suffix == "-start"),
            payload_bytes=sum(n * _DTYPE_BYTES[dt] for dt, n in payload),
            dtypes=tuple(sorted({dt for dt, _ in payload})),
            replica_groups=_parse_groups(
                g.group(1)) if (g := _REPLICA_GROUPS_RE.search(attrs)) else [],
            source_target_pairs=[
                tuple(p) for p in _parse_groups(g.group(1))
            ] if (g := _SOURCE_TARGET_RE.search(attrs)) else [],
        ))

    per_kind: dict[str, int] = {}
    count: dict[str, int] = {}
    for op in collectives:
        per_kind[op.kind] = per_kind.get(op.kind, 0) + op.payload_bytes
        count[op.kind] = count.get(op.kind, 0) + 1

    return {
        "collectives": collectives,
        "bytes_per_kind": per_kind,
        "count_per_kind": count,
        "total_bytes": int(sum(per_kind.values())),
        "scatter_ops": scatter_ops,
        "convert_ops": convert_ops,
        "input_output_alias": input_output_aliases_from_hlo(hlo_text),
    }


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Historical interface: {bytes_per_kind, count_per_kind, total_bytes}.

    Same walk as `analyze_hlo`, so the dry-run harness, the benchmarks and
    the analysis rules agree byte-for-byte.
    """
    res = analyze_hlo(hlo_text)
    return {"bytes_per_kind": res["bytes_per_kind"],
            "count_per_kind": res["count_per_kind"],
            "total_bytes": res["total_bytes"]}


def input_output_aliases_from_hlo(hlo_text: str) -> list:
    """[(output_index, parameter_number)] pairs the executable aliased.

    Parsed from the HloModule header's `input_output_alias={ {o}: (p, {},
    may-alias) }` section — present (even on XLA:CPU) exactly when the
    compiled program declared donated/aliased arguments.
    """
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias=")
    depth = 0
    end = i
    for j in range(i, min(len(hlo_text), i + 4096)):
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                end = j + 1
                break
    section = hlo_text[i:end]
    pairs = []
    for om, pm in re.findall(r"\{([0-9,\s]*)\}:\s*\((\d+)", section):
        out_idx = tuple(int(t) for t in om.split(",") if t.strip())
        pairs.append((out_idx if len(out_idx) != 1 else out_idx[0], int(pm)))
    return pairs


def op_names_from_hlo(hlo_text: str) -> dict:
    """{instruction name: op_name} over every computation of the module.

    The op_name is JAX's name stack for the op the instruction came from
    (a fusion carries its root's), e.g.
    `jit(step)/transpose(jvp(edge.gather))/scatter-add`. Instructions with
    no op_name (copies, bitcasts, constants, tuples) are left out.
    """
    return dict(_OP_NAME_RE.findall(hlo_text))
