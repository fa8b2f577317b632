"""jaxpr-level lint: compile-time hazards visible before XLA runs.

Walks a ``ClosedJaxpr`` (the output of ``jax.make_jaxpr`` — tracing
only, no XLA compile, so this pass is cheap enough for tight test
loops) and flags the hazard classes that previous PRs root-caused by
hand:

- **TD001 dense closure constant**: a concrete array closed over by the
  traced function lands in ``ClosedJaxpr.consts`` and is embedded into
  the lowered module as a dense HLO constant. At Higgs scale the fused
  step's closed-over bin matrix was a ~300 MB constant per program plus
  XLA constant-folding stalls (PR 3); the fix was passing the arrays as
  arguments (``gbdt._fused_data_args``), and this rule keeps it fixed.
- **TD002 host callback**: ``debug_callback`` / ``pure_callback`` /
  ``io_callback`` primitives staged into a hot-path program force a
  host round-trip per dispatch — sync-free dispatch-ahead training is
  impossible with one in the trace.
- **TD003 dtype widening**: ``convert_element_type`` to f64 inside
  traced code. The repo's numerics are f32/bf16/int8 by design (PARITY
  holds at f32); an accidental f64 op doubles bandwidth on TPU and
  silently de-pairs results from the reference.
- **TD004 CPU donation**: ``pjit`` equations carrying donated invars
  while the backend is CPU. Zero-copy ``np.asarray`` views of CPU jax
  arrays alias the donated buffers, so the next in-place write corrupts
  live host views (the PR-3 corrupted-valid-metrics incident); the
  trainer pins no-donate on CPU and this rule enforces it repo-wide.
- **TD006 eager guard flag**: the fused step's deferred stop/NaN flags
  missing from the program outputs. The no-split stop AND the numeric-
  divergence guard (``nan_guard``, the resilience PR) are deferred
  device booleans read in ONE batched ``device_get`` at sync points; an
  implementation that checks either one eagerly (``bool(flag)`` /
  ``float(x)`` inside the dispatch path) collapses dispatch-ahead to a
  host sync per iteration. The rule asserts the traced step exposes the
  expected number of scalar-bool outvars — a flag that was synced
  eagerly no longer appears as a program output.

- **TD005 class-unrolled build**: more than ``max_build_programs``
  tree-grow ``while`` loops staged under the ``build`` profiler phase.
  A multiclass iteration that unrolls ``for k in range(K)`` stages K
  complete builds per program — trace size, XLA compile time and the
  sequential kernel chain all scale O(num_class) (the regression the
  ``class_batch`` knob removes, ISSUE 8). The class-batched build
  stages exactly ONE (vmapped) grow loop, so callers that know the
  gate is open pass ``max_build_programs=1``. This rule is
  jaxpr-level only: in compiled HLO all K unrolled copies share the
  same source location, so post-CSE ``op_name`` metadata collapses
  them and the duplication is no longer countable (verified
  empirically on the CPU backend — the K grow loops lower with
  scatter-expansion metadata, not distinct build tags).
- **TD008 per-row table read**: inside the ``build`` stage, a
  ``gather`` of at least R index tuples from operand dimensions that
  hold fewer than R entries together, or a ``scatter-add`` of at least
  R updates into fewer than R segments (R = the rows a device holds).
  On a v5e a gathered element costs 9.7 ns: six ``[L+1]`` tables read
  by ``row_leaf`` were 12.0 s of an 18.3 s Higgs tree, the
  ``segment_sum`` of R ones another 1.7 s (PERF.md section 6, PR 29).
  What a row needs of its leaf is W records, selected by W compares
  (``tree_builder.select_by_slot``), and a count of the rows in S
  slots is one compare-and-sum (``slot_counts``). Gathers by the
  compacted stream's index read R-sized operands and pass; the index
  itself is a sort of the row numbers
  (``tree_builder.stream_index``), neither a gather nor a scatter.
  The histogram itself (``hist_kernel`` scope) is the one sum over rows
  a tree needs and is exempt: on the chip it is a Pallas kernel with a
  roofline metric of its own; the XLA scatter formulation that CPU
  tests use adds rows into ``S*F*B`` bins by design.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from .report import TraceReport

__all__ = ["lint_jaxpr", "lint_deferred_guard", "iter_eqns",
           "count_build_loops", "per_row_table_reads",
           "CALLBACK_PRIMITIVES", "DEFAULT_CONST_BYTES"]

# primitive names that round-trip through the host per dispatch
CALLBACK_PRIMITIVES = frozenset({
    "debug_callback", "pure_callback", "io_callback",
    "outside_call", "host_callback_call", "debug_print"})

# floats narrower than f64 — widening any of these to f64 is TD003
_NARROW_FLOATS = ("float32", "bfloat16", "float16")

DEFAULT_CONST_BYTES = 1 << 20       # 1 MiB


def _sub_jaxprs(params):
    """Nested jaxprs of one equation's params (pjit/scan/while carry a
    single `jaxpr`; cond carries `branches`; custom_* carry call
    jaxprs). Yields ClosedJaxpr-or-Jaxpr values."""
    for v in params.values():
        if hasattr(v, "jaxpr") or hasattr(v, "eqns"):
            yield v
        elif isinstance(v, (list, tuple)):
            for vv in v:
                if hasattr(vv, "jaxpr") or hasattr(vv, "eqns"):
                    yield vv


def iter_eqns(jaxpr):
    """Depth-first over every equation, descending into nested call /
    control-flow jaxprs (pjit, scan, while, cond branches, shard_map,
    custom_jvp/vjp)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            yield from iter_eqns(inner)


_BUILD_SCOPE = None     # compiled lazily (module import must not need re)


def count_build_loops(jaxpr, prefix: str = "") -> int:
    """Number of tree-grow ``while`` loops staged under the ``build``
    profiler phase (TD005's counting pass).

    ``name_stack`` is NOT inherited by nested call jaxprs —
    the ``pjit``/``shard_map`` equation itself carries the scope and its
    sub-jaxpr equations start empty — so the walk threads the
    accumulated stack down as ``prefix``. Batching renames the scope
    (``vmap(build)``/``transpose(build)``), hence the word-boundary
    match rather than a prefix compare. A counted build loop's OWN
    nested loops (blocked histogram scans etc.) belong to that build,
    so the walk does not descend into them.
    """
    import re
    global _BUILD_SCOPE
    if _BUILD_SCOPE is None:
        _BUILD_SCOPE = re.compile(r"\bbuild\b")
    n = 0
    for eqn in jaxpr.eqns:
        stack = str(getattr(eqn.source_info, "name_stack", "") or "")
        full = "/".join(s for s in (prefix, stack) if s)
        if eqn.primitive.name == "while" and _BUILD_SCOPE.search(full):
            n += 1
            continue
        for sub in _sub_jaxprs(eqn.params):
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            n += count_build_loops(inner, full)
    return n


def _iter_scoped(jaxpr, prefix: str = ""):
    """``(equation, full name stack)`` depth-first. ``name_stack`` is
    not inherited by nested call jaxprs (see :func:`count_build_loops`),
    so the accumulated stack is threaded down."""
    for eqn in jaxpr.eqns:
        stack = str(getattr(eqn.source_info, "name_stack", "") or "")
        full = "/".join(s for s in (prefix, stack) if s)
        yield eqn, full
        for sub in _sub_jaxprs(eqn.params):
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            yield from _iter_scoped(inner, full)


def per_row_table_reads(jaxpr, rows: int):
    """TD008's pass: ``(primitive, name stack, index tuples, entries)``
    of every ``gather`` / ``scatter-add`` under the ``build`` stage and
    outside ``hist_kernel`` whose indices number at least ``rows``
    while the operand dimensions they index hold fewer than ``rows``
    entries."""
    import math
    import re

    from ..phases import BUILD, BUILD_STAGES, HIST_KERNEL
    # a tree build traced on its own (doctor_tree_builder) carries the
    # stage names without the fused step's ``build`` around them
    build = re.compile(r"\b(%s)\b" % "|".join(
        sorted({BUILD} | BUILD_STAGES)))
    kernel = re.compile(r"\b%s\b" % HIST_KERNEL)
    out = []
    for eqn, stack in _iter_scoped(jaxpr):
        name = eqn.primitive.name
        if name not in ("gather", "scatter-add"):
            continue
        if not build.search(stack) or kernel.search(stack):
            continue
        dn = eqn.params["dimension_numbers"]
        dims = (dn.start_index_map if name == "gather"
                else dn.scatter_dims_to_operand_dims)
        operand, indices = eqn.invars[0].aval, eqn.invars[1].aval
        n_idx = math.prod(indices.shape[:-1])
        entries = math.prod(operand.shape[d] for d in dims)
        if n_idx >= rows and entries < rows:
            out.append((name, stack, n_idx, entries))
    return out


def _const_entries(closed):
    """(index, const) for the top-level consts plus nested pjit consts
    (a closure constant can hide one jit level down)."""
    out = list(enumerate(closed.consts))
    base = len(out)
    for eqn in iter_eqns(closed.jaxpr):
        sub = eqn.params.get("jaxpr")
        if sub is not None and hasattr(sub, "consts"):
            for c in sub.consts:
                out.append((base, c))
                base += 1
    return out


def lint_jaxpr(closed, *, label: str,
               max_const_bytes: int = DEFAULT_CONST_BYTES,
               allow_callbacks: bool = False,
               backend: Optional[str] = None,
               max_build_programs: Optional[int] = None,
               build_rows: Optional[int] = None,
               allow: Sequence[Tuple[str, str]] = ()) -> TraceReport:
    """Lint one ``ClosedJaxpr``; returns the :class:`TraceReport`.

    ``allow_callbacks`` relaxes TD002 for programs where a callback is
    the point (debug harnesses); ``backend`` defaults to
    ``jax.default_backend()`` and gates TD004 (donation is the right
    call on accelerators — only CPU aliases host views).
    ``max_build_programs`` enables TD005: the program may stage at most
    that many ``build``-phase grow loops (1 for a class-batched or
    single-class trainer; ``None`` skips the rule for programs with a
    legitimate sequential fallback — linear trees, forced splits,
    CEGB). ``build_rows`` enables TD008: the rows one device holds
    (after padding), against which the ``build`` stage's gathers and
    scatter-adds are sized.
    """
    import jax
    rep = TraceReport(label=label)
    backend = backend or jax.default_backend()

    # TD001 — dense closure constants
    for idx, c in _const_entries(closed):
        shape = getattr(c, "shape", None)
        dtype = getattr(c, "dtype", None)
        if shape is None or dtype is None:
            continue
        nbytes = int(getattr(c, "size", 0)) * dtype.itemsize
        if nbytes >= max_const_bytes:
            rep.add("TD001", "error", f"const[{idx}]",
                    f"dense {dtype} {tuple(shape)} closure constant "
                    "embedded in the program; pass it as an argument "
                    "(see gbdt._fused_data_args)", nbytes=nbytes)

    donated_seen = False
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        # TD002 — host callbacks
        if name in CALLBACK_PRIMITIVES and not allow_callbacks:
            rep.add("TD002", "error", name,
                    "host callback staged into a hot-path program; "
                    "each dispatch round-trips through Python")
        # TD003 — f64 widening
        if name == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            src = str(eqn.invars[0].aval.dtype) \
                if eqn.invars and hasattr(eqn.invars[0], "aval") else ""
            if new == "float64" and src in _NARROW_FLOATS:
                rep.add("TD003", "error", name,
                        f"dtype widening {src} -> float64 inside "
                        "traced code; the repo's numerics are "
                        "f32/bf16/int8 by design")
        # TD004 — donation on CPU
        if name == "pjit" and not donated_seen:
            if any(eqn.params.get("donated_invars") or ()):
                donated_seen = True
                if backend == "cpu":
                    rep.add(
                        "TD004", "error", f"pjit:{eqn.params.get('name', '')}",
                        "buffer donation compiled on the CPU backend: "
                        "zero-copy np.asarray views alias donated "
                        "buffers and the next in-place write corrupts "
                        "them (gate donation on "
                        "jax.default_backend() != 'cpu')")

    # TD005 — class-unrolled build
    if max_build_programs is not None:
        n = count_build_loops(closed.jaxpr)
        if n > max_build_programs:
            rep.add(
                "TD005", "error", "build",
                f"class-unrolled build: {n} build-phase grow loops "
                f"staged in one program (budget {max_build_programs}); "
                "per-class tree builds should batch over the class "
                "axis into ONE vmapped loop (class_batch=auto), not "
                "unroll for k in range(num_class)")
    # TD008 — per-row reads of a small table in the build stage
    if build_rows is not None:
        for name, stack, n_idx, entries in per_row_table_reads(
                closed.jaxpr, build_rows):
            rep.add(
                "TD008", "error", f"{stack}/{name}",
                f"{name} of {n_idx} index tuples (rows a device: "
                f"{build_rows}) over {entries} table entries in the "
                "build stage: on the chip each element is a serial "
                "lookup; select the round's W records by comparing "
                "row_leaf with its W slots (tree_builder."
                "select_by_slot), count slots by slot_counts")
    return rep.apply_allowlist(allow)


def lint_deferred_guard(closed, *, label: str,
                        expect_flags: int = 2,
                        allow: Sequence[Tuple[str, str]] = ()
                        ) -> TraceReport:
    """TD006: the fused step's deferred flags must be PROGRAM OUTPUTS.

    The no-split stop and the NaN guard each ride the dispatch as a
    scalar-bool outvar, read together in sync()'s one batched
    ``device_get``. Counting scalar-bool outputs of the traced step
    catches the regression where a guard implementation syncs its flag
    eagerly (``bool(ok)`` in the dispatch path): the flag then never
    reaches the program interface, dispatch-ahead collapses to one
    host round-trip per iteration, and ``host_syncs_per_iter`` between
    eval points stops being 0.
    """
    rep = TraceReport(label=label)
    n = 0
    for var in closed.jaxpr.outvars:
        aval = getattr(var, "aval", None)
        if aval is None:
            continue
        if getattr(aval, "shape", None) == () \
                and str(getattr(aval, "dtype", "")) == "bool":
            n += 1
    if n < expect_flags:
        rep.add(
            "TD006", "error", "deferred_flags",
            f"{n} scalar-bool program output(s), expected "
            f">= {expect_flags} (no-split stop + nan_guard finite "
            "flag); a guard checked eagerly inside the dispatch path "
            "drops its flag from the program interface and forces a "
            "host sync per iteration")
    return rep.apply_allowlist(allow)
