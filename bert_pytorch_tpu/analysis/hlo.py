"""Structured reports over lowered/compiled XLA programs.

The worst regressions this repo has hit were *program-structure* bugs that
no unit test could see until a multichip run: fail-open sharding
gates (round 7), GSPMD forking the ZeRO-1 gather into extra all-gathers
(round 11, until now guarded only by one ad-hoc regex in
tests/test_zero1.py), meshes whose time went mostly to collectives. The
compiled program is a perfectly inspectable artifact — `jit(f).lower(...)
.compile().as_text()` is stable HLO text — so this module parses it into a
structured report the rule framework (analysis/passes.py) and the CI gate
(tools/graphcheck.py) consume:

- collective inventory: all-gather / all-reduce / reduce-scatter /
  collective-permute / all-to-all counts, result shapes, bytes, replica
  group sizes, and an estimated bytes-moved figure per kind;
- copy/transpose/fusion/dot op counts (the layout-regression smells the
  round-6 kernel work was chasing);
- kernel inventory: the Pallas kernels in the program (`tpu_custom_call`
  instructions) counted by the name their `pallas_call` gave them — how a
  run proves it took the flash / LayerNorm kernels and not the XLA path;
- scope inventory: instructions under each given path of the program's
  named scopes (training/pretrain.STEP_SUBSCOPES), by their `op_name` — how
  a run proves its executable carries the scopes its per-layer metrics read
  (a warm persistent cache can serve an older program's metadata);
- the input→output buffer-donation table: which donated parameters XLA
  actually aliased (`input_output_alias`) vs accepted-but-never-aliased
  (`buffer_donor` — the double-HBM miss `donate_argnums` silently allows);
- per-input leaf table (paths from the argument pytree, compiled
  in-shardings, expected shardings from the parallel plan) for the
  unexpected-replication pass;
- a `fingerprint` (collective counts + donation summary hash) small enough
  to ride in flight-recorder manifests and MetricLogger run headers, so
  tools/replay.py can warn when a replayed program's structure diverges
  from the recorded one.

Everything that parses TEXT is stdlib-only and importable without jax
(tools/graphcheck.py --validate-budgets relies on this); the helpers that touch compiled objects or pytrees
import jax lazily inside the function.
"""

from __future__ import annotations

import hashlib
import json
import re
from typing import Any, Dict, List, Optional, Sequence

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")

# layout/fusion smells tracked alongside the collectives
TRACKED_OPS = ("copy", "transpose", "fusion", "dot", "dynamic-update-slice")

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# one HLO instruction: `%name = <result-shape> opcode(operands...)`.
# The result shape is a tuple `(f32[..]{..}, ...)` or a single
# `dtype[dims]{layout}`. A TPU layout carries parentheses of its own
# (`{1,0:T(8,128)(2,1)S(1)}`: tiling, memory space) but never a lowercase
# word followed by `(` after whitespace — so the opcode is the first such
# word after the `=`.
_INSTR_RE = re.compile(r"=\s.*?\s(?P<op>[a-z][a-z0-9-]*)\(")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

# `replica_groups=[8,1]<=[8]` (iota form: [n_groups, group_size]) or the
# explicit `replica_groups={{0,1},{2,3}}` form
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")

# a Mosaic (Pallas TPU) kernel, and the jax op path in its metadata: a
# pallas_call(name=...) shows as `.../<name>/pallas_call`
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_INNERMOST_RE = re.compile(r"\(([^()]*)\)+$")


def scope_pattern(path: str) -> "re.Pattern":
    """Matches an `op_name` under the scope `path`: its `/`-joined
    components each a whole component of the name and next to each other; a
    transform's wrapper (`transpose(jvp(attention))/qkv`) does not hide one.
    The benchmark's readers/scope_sum_share.under is the same pattern
    (tests/test_step_scopes.py holds the two equal)."""
    parts = [re.escape(p) + r"\)*" for p in path.split("/")]
    return re.compile(r"(?:^|[/(])" + "/".join(parts) + r"(?:/|$)")


_ALIAS_ENTRY_RE = re.compile(
    r"\{[0-9,\s]*\}:\s*\(\s*(\d+)\s*,\s*\{[0-9,\s]*\}\s*(?:,\s*[\w-]+\s*)?\)")
_DONOR_ENTRY_RE = re.compile(r"\(\s*(\d+)\s*,\s*\{[0-9,\s]*\}\s*\)")


def _result_shapes(line: str, async_start: bool = False) -> list:
    """(dtype, dims) pairs of the instruction's result shape(s) — the
    text between '=' and the opcode. `async_start`: an async collective's
    tuple result is `(operand_buffer, output)` — only the LAST element is
    the collective's output; counting the whole tuple would double-count
    the traffic (~2x on all-reduce-start)."""
    m = _INSTR_RE.search(line)
    lhs = (line[line.index("=") + 1:m.start("op")] if m is not None
           else line.split("=", 1)[1])
    shapes = _SHAPE_RE.findall(lhs)
    if async_start and len(shapes) > 1:
        # the TPU compiler appends u32[] context scalars after the output
        # (collective-permute-start: (operand, output, u32[], u32[]))
        while len(shapes) > 2 and shapes[-1] == ("u32", ""):
            shapes.pop()
        shapes = shapes[-1:]
    return shapes


def _shapes_bytes(shapes: list) -> int:
    total = 0
    for dt, dims in shapes:
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, num_partitions: Optional[int]) -> Optional[int]:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([t for t in m.group(1).strip("{}").split(",") if t.strip()])
    return num_partitions


def _braced_segment(text: str, opener: str) -> Optional[str]:
    """The balanced-brace body following `opener` (which ends with '{'),
    or None when the opener is absent. Entries inside the module-header
    tables contain nested braces (`{0}: (0, {}, may-alias)`), so a split
    on '}' under-reads — count depth instead."""
    start = text.find(opener)
    if start < 0:
        return None
    depth, i = 1, start + len(opener)
    while i < len(text) and depth:
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
        i += 1
    return text[start + len(opener):i - 1]


def _est_bytes_moved(kind: str, bytes_out: int, group_size: Optional[int]
                     ) -> int:
    """Rough per-participant wire bytes for one collective (ring algorithm
    estimates — attribution fodder, not a profiler): all-gather receives
    (g-1)/g of its output, all-reduce moves ~2x that (reduce-scatter +
    gather phases), reduce-scatter's input is g x its output, a permute
    moves its full payload."""
    g = group_size or 2
    if g <= 1:
        return 0
    if kind == "all-gather":
        return bytes_out * (g - 1) // g
    if kind == "all-reduce":
        return 2 * bytes_out * (g - 1) // g
    if kind == "reduce-scatter":
        return bytes_out * (g - 1)
    return bytes_out  # collective-permute / all-to-all


def parse_hlo_module(text: str, scopes: Sequence[str] = ()
                     ) -> Dict[str, Any]:
    """Compiled HLO text -> the structural summary (stdlib only).

    Counts opcodes (async `-start` forms count once; `-done` halves are
    skipped so nothing double-counts), sizes collective results, and parses
    the module header's donation tables. With `scopes` (paths of named
    scopes), also `scope_counts`: the instructions whose `op_name` is under
    each, in the same pass. Deterministic for fixed input.
    """
    scope_counts: Dict[str, int] = {path: 0 for path in scopes}
    scope_pats = [(path, scope_pattern(path)) for path in scopes]
    scopes_of: Dict[str, tuple] = {}    # op_name -> the paths it is under
    counts: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    op_counts: Dict[str, int] = {k: 0 for k in TRACKED_OPS}
    coll_bytes: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    est_moved: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    shapes: Dict[str, int] = {}
    kernels: Dict[str, int] = {}
    num_partitions = None
    header = ""
    for line in text.splitlines():
        if not header and line.startswith("HloModule"):
            header = line
            m = re.search(r"num_partitions=(\d+)", line)
            if m:
                num_partitions = int(m.group(1))
            continue
        m = _INSTR_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        if scope_pats:
            named = _OP_NAME_RE.search(line)
            if named is not None:
                name = named.group(1)
                under = scopes_of.get(name)
                if under is None:
                    under = scopes_of[name] = tuple(
                        path for path, pat in scope_pats if pat.search(name))
                for path in under:
                    scope_counts[path] += 1
        if op == "custom-call" and _KERNEL_TARGET in line:
            name = _kernel_name(line)
            kernels[name] = kernels.get(name, 0) + 1
            continue
        if op.endswith("-done"):
            continue
        base = op[:-6] if op.endswith("-start") else op
        if base in counts:
            counts[base] += 1
            out_shapes = _result_shapes(line, async_start=(base != op))
            b = _shapes_bytes(out_shapes)
            coll_bytes[base] += b
            gs = _group_size(line, num_partitions)
            est_moved[base] += _est_bytes_moved(base, b, gs)
            if out_shapes:
                dt, dims = out_shapes[0]
                key = f"{base} {dt}[{dims}]"
            else:
                key = base
            shapes[key] = shapes.get(key, 0) + 1
        elif base in op_counts:
            op_counts[base] += 1

    donation = {"aliased": [], "donated_unaliased": []}
    seg = _braced_segment(header, "input_output_alias={")
    if seg is not None:
        donation["aliased"] = sorted(
            {int(p) for p in _ALIAS_ENTRY_RE.findall(seg)})
    seg = _braced_segment(header, "buffer_donor={")
    if seg is not None:
        donation["donated_unaliased"] = sorted(
            {int(p) for p in _DONOR_ENTRY_RE.findall(seg)})
    donation["n_aliased"] = len(donation["aliased"])
    donation["n_donated_unaliased"] = len(donation["donated_unaliased"])

    return {
        "num_partitions": num_partitions,
        "collective_counts": counts,
        "collective_bytes": coll_bytes,
        "collective_est_bytes_moved": est_moved,
        "collective_shapes": dict(sorted(shapes.items())),
        "op_counts": op_counts,
        "kernel_counts": dict(sorted(kernels.items())),
        "donation": donation,
        # only where asked for: the checked-in graph reports carry none
        **({"scope_counts": scope_counts} if scopes else {}),
    }


def _kernel_name(line: str) -> str:
    """Name of the Pallas kernel behind one tpu_custom_call instruction:
    the op-path component before `pallas_call`, or "unnamed"."""
    m = _OP_NAME_RE.search(line)
    parts = m.group(1).split("/") if m else []
    if "pallas_call" in parts[1:]:
        # autodiff wraps the name: `transpose(jvp(flash_bwd_dqkv))`; an
        # unnamed call leaves an empty innermost pair: `jvp()`
        scope = parts[parts.index("pallas_call", 1) - 1]
        inner = _INNERMOST_RE.search(scope)
        name = inner.group(1) if inner else scope
        if name and not scope.startswith("jit("):
            return name
    return "unnamed"


def kernel_counts(text: str) -> Dict[str, int]:
    """{kernel name: instructions} of the Pallas TPU kernels in an HLO
    text (empty on backends that run no Mosaic kernel)."""
    return parse_hlo_module(text)["kernel_counts"]


def collective_counts(text: str) -> Dict[str, int]:
    """Just the per-kind collective counts of an HLO text — the one
    counter tests/test_zero1.py and the budget pass share (replacing the ad-hoc per-test regexes)."""
    return parse_hlo_module(text)["collective_counts"]


def collective_inventory(text: str) -> Dict[str, Any]:
    """Counts + bytes + estimated wire traffic of one program."""
    rep = parse_hlo_module(text)
    return {
        "counts": {k: v for k, v in rep["collective_counts"].items() if v},
        "bytes_out": {k: v for k, v in rep["collective_bytes"].items() if v},
        "est_bytes_moved": {
            k: v for k, v in rep["collective_est_bytes_moved"].items() if v},
        "shapes": rep["collective_shapes"],
    }


def stablehlo_dot_dtypes(lowered_text: str) -> Dict[str, int]:
    """Result element types of every dot/convolution in the LOWERED
    (StableHLO) program. The dtype lint must read the pre-optimization
    text: backends legally rewrite dtypes after this point (the CPU
    backend upcasts bf16 matmuls to f32 wholesale), so only the lowering
    reflects what the model code asked for."""
    out: Dict[str, int] = {}
    pat = re.compile(
        r"stablehlo\.(?:dot_general|dot|convolution)\b[^\n]*->\s*"
        r"tensor<([^>]*)>")
    for m in pat.finditer(lowered_text):
        elem = m.group(1).split("x")[-1]
        out[elem] = out.get(elem, 0) + 1
    return out


# -- jax-side report assembly -------------------------------------------------


def sharding_leaves(tree: Any, expected: Optional[Sequence] = None,
                    ) -> List[Dict[str, Any]]:
    """Per-leaf sharding table of a pytree of concrete arrays, Shape-
    DtypeStructs-with-sharding, or NamedShardings: path, shape, bytes,
    actual spec + replicated flag, per-device bytes, and (optionally) the
    expected sharding. `expected` is a flat sequence aligned with the
    tree's flatten order — entries are NamedShardings (what the plan says
    this leaf's layout should be) or None (no expectation). This is the
    one leaf walk behind parallel/zero.assert_moments_sharded, the K-FAC
    shard audit, and the compiled-program replication pass."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    if expected is not None and len(expected) != len(flat):
        raise ValueError(
            f"expected-sharding list has {len(expected)} entries for "
            f"{len(flat)} tree leaves — derive it from the same tree")
    rows: List[Dict[str, Any]] = []
    for i, (path, leaf) in enumerate(flat):
        sh = getattr(leaf, "sharding", None) \
            if not _is_sharding(leaf) else leaf
        shape = tuple(getattr(leaf, "shape", ()) or ())
        dtype = getattr(leaf, "dtype", None)
        try:
            import numpy as np

            itemsize = np.dtype(dtype).itemsize if dtype is not None else 0
        except TypeError:
            itemsize = getattr(dtype, "itemsize", 0) or 0
        nbytes = itemsize
        for d in shape:
            nbytes *= d
        row: Dict[str, Any] = {
            "path": jax.tree_util.keystr(path),
            "shape": list(shape),
            "dtype": str(dtype) if dtype is not None else None,
            "bytes": int(nbytes),
            "spec": None,
            "replicated": None,
            "per_device_bytes": int(nbytes),
        }
        if sh is not None and hasattr(sh, "is_fully_replicated"):
            row["replicated"] = bool(sh.is_fully_replicated)
            if hasattr(sh, "spec"):
                row["spec"] = str(sh.spec)
            if shape and hasattr(sh, "shard_shape"):
                try:
                    local = sh.shard_shape(shape)
                    per = itemsize
                    for d in local:
                        per *= d
                    row["per_device_bytes"] = int(per)
                except Exception:
                    pass
        if expected is not None:
            exp = expected[i]
            if exp is not None and hasattr(exp, "is_fully_replicated"):
                row["expected_spec"] = str(getattr(exp, "spec", exp))
                row["expected_sharded"] = not exp.is_fully_replicated
                if sh is not None:
                    # the sharding_rules pass contract: True/False when a
                    # comparison happened, absent otherwise. Equivalence,
                    # not string equality — trivial mesh axes and trailing
                    # None entries must not count as violations.
                    try:
                        row["matches_expected"] = bool(
                            sh.is_equivalent_to(exp, len(shape)))
                    except Exception:
                        row["matches_expected"] = (
                            str(getattr(sh, "spec", sh))
                            == str(getattr(exp, "spec", exp)))
            else:
                row["expected_spec"] = None
                row["expected_sharded"] = False
        rows.append(row)
    return rows


def _is_sharding(x: Any) -> bool:
    return type(x).__name__.endswith("Sharding")


def program_report(compiled: Any, args: Optional[tuple] = None,
                   expected: Optional[Sequence] = None,
                   lowered_text: Optional[str] = None,
                   label: Optional[str] = None,
                   rules: Optional[Sequence[Optional[str]]] = None
                   ) -> Dict[str, Any]:
    """Full structured report of one compiled program.

    `compiled` is a jax.stages.Compiled (from jit(f).lower(...).compile()).
    `args` (the example args the program was lowered with) adds the
    per-input leaf table with paths + compiled in-shardings; `expected` is
    the flat expected-sharding list for those args (sharding_leaves
    contract — each comparison lands as the row's `matches_expected`
    bool, what the sharding_rules pass gates). `rules`, aligned with
    `expected`, stamps each row with the rules-table label that derived
    its expectation (parallel/rules.py), so a finding can name the rule.
    `lowered_text` (lowered.as_text(), StableHLO) adds the dot-dtype
    census the dtype lint reads.
    """
    rep = parse_hlo_module(compiled.as_text())
    rep["label"] = label
    if lowered_text is not None:
        rep["dot_dtypes"] = stablehlo_dot_dtypes(lowered_text)
    try:
        ma = compiled.memory_analysis()
        rep["memory"] = {
            k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    except Exception:
        rep["memory"] = None
    if args is not None:
        import jax

        # align the executable's input shardings with the arg tree.
        # Two kinds of None complicate this: keep_unused=False PRUNES
        # unused args (their slot in input_shardings is None while the arg
        # tree has a real leaf), and structural Nones (empty optional
        # fields, e.g. TrainState.precond_state) appear in BOTH trees.
        # Flatten both with None-as-leaf, drop the structural pairs, and
        # what remains lines up 1:1 with the default tree_leaves order —
        # the order `expected` is derived in.
        none_leaf = {"is_leaf": lambda x: x is None}
        in_sh = jax.tree_util.tree_leaves(compiled.input_shardings[0],
                                          **none_leaf)
        flat = jax.tree_util.tree_flatten_with_path(args, **none_leaf)[0]
        if len(in_sh) == len(flat):
            triples = [(p, a, s) for (p, a), s in zip(flat, in_sh)
                       if a is not None]
            rows = []
            for i, (path, a, s) in enumerate(triples):
                row_tree = jax.ShapeDtypeStruct(
                    getattr(a, "shape", ()), getattr(a, "dtype", None),
                    sharding=s)
                row = sharding_leaves(
                    [row_tree],
                    expected=[expected[i]] if expected is not None
                    else None)[0]
                row["path"] = jax.tree_util.keystr(path)
                if rules is not None and i < len(rules) \
                        and rules[i] is not None:
                    row["rule"] = rules[i]
                rows.append(row)
            aliased = set(rep["donation"]["aliased"])
            unaliased = set(rep["donation"]["donated_unaliased"])
            # executable parameter numbers count only the KEPT args
            # (pruned ones have a None sharding slot)
            param = 0
            for row, (_, _, s) in zip(rows, triples):
                if s is None:
                    row["pruned"] = True
                    continue
                row["param"] = param
                row["aliased"] = param in aliased
                if param in unaliased:
                    row["donated_unaliased"] = True
                param += 1
            rep["inputs"] = rows
    return rep


# -- fingerprint ---------------------------------------------------------------


def _short_hash(obj: Any) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


def fingerprint_of(report: Dict[str, Any]) -> Dict[str, Any]:
    """Compact structural identity of a program report: collective counts
    plus a donation-summary hash. Small enough for a flight-recorder
    manifest or a MetricLogger run header; tools/replay.py compares the
    recorded one against the replayed program's."""
    donation = report.get("donation", {})
    dsum = {"aliased": donation.get("aliased", []),
            "donated_unaliased": donation.get("donated_unaliased", [])}
    counts = {k: v for k, v in
              report.get("collective_counts", {}).items() if v}
    fp = {
        "collective_counts": counts,
        "n_aliased": donation.get("n_aliased", 0),
        "n_donated_unaliased": donation.get("n_donated_unaliased", 0),
        "donation_hash": _short_hash(dsum),
        "num_partitions": report.get("num_partitions"),
        "hash": _short_hash({"collectives": counts, "donation": dsum}),
    }
    if report.get("kernel_counts"):
        # informational (not part of the hash): which Pallas kernels the
        # program runs. Absent where it runs none, e.g. every CPU program
        fp["kernel_counts"] = report["kernel_counts"]
    if report.get("scope_counts"):
        # informational too: instructions under each declared sub-scope
        fp["scope_counts"] = report["scope_counts"]
    return fp


def stale_scopes_warning(fp: Dict[str, Any]) -> Optional[str]:
    """What to tell the operator when a declared scope counts no
    instruction in the executable, or None. JAX keeps `op_name`s out of the
    persistent compile cache's key, so two programs that differ only in
    named scopes share an entry and whichever compiled first gives the
    executable its metadata: a trace of such a run reads the older
    program's scopes."""
    missing = [path for path, n in fp.get("scope_counts", {}).items()
               if n == 0]
    if not missing:
        return None
    return ("executable older than the program's scopes: no instruction "
            f"under {', '.join(missing)} (the compile cache served an "
            "executable compiled before these scopes were opened, and the "
            "per-layer metrics that read them will be silent): clear the "
            "compile cache before tracing")


def program_fingerprint(compiled: Any, scopes: Sequence[str] = ()
                        ) -> Dict[str, Any]:
    """fingerprint_of(parse) straight from a compiled object, stamped with
    the live platform (fingerprints are only comparable same-platform —
    backends lower to different collective schedules). `scopes`: as
    parse_hlo_module's."""
    fp = fingerprint_of(parse_hlo_module(compiled.as_text(), scopes))
    try:
        import jax

        fp["platform"] = jax.devices()[0].platform
    except Exception:
        fp["platform"] = None
    return fp


def compare_fingerprints(recorded: Optional[Dict[str, Any]],
                         replayed: Optional[Dict[str, Any]]
                         ) -> tuple[bool, List[str]]:
    """(comparable, diffs). Not comparable when either side is missing or
    platform/partition count differ (a CPU replay of a TPU bundle is a
    different backend's schedule, not a regression). Comparable with empty
    diffs = same program structure."""
    if not recorded or not replayed:
        return False, []
    for k in ("platform", "num_partitions"):
        if recorded.get(k) != replayed.get(k):
            return False, [f"{k}: recorded {recorded.get(k)} vs "
                           f"replayed {replayed.get(k)} (not comparable)"]
    diffs: List[str] = []
    rc = recorded.get("collective_counts", {})
    pc = replayed.get("collective_counts", {})
    for kind in sorted(set(rc) | set(pc)):
        if rc.get(kind, 0) != pc.get(kind, 0):
            diffs.append(f"collective {kind}: recorded {rc.get(kind, 0)} "
                         f"vs replayed {pc.get(kind, 0)}")
    if recorded.get("donation_hash") != replayed.get("donation_hash"):
        diffs.append(
            f"donation summary: recorded {recorded.get('n_aliased')} "
            f"aliased/{recorded.get('n_donated_unaliased')} missed vs "
            f"replayed {replayed.get('n_aliased')}/"
            f"{replayed.get('n_donated_unaliased')}")
    return True, diffs
