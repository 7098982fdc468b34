"""Compiled scoring — the fitted transformer DAG as ONE XLA program.

The reference's score path bulk-applies row closures per layer and persists
every K stages to break Catalyst (FitStagesUtil.scala:96,134-165).  Here
every maximal device-resident stretch of the DAG — vectorizer models,
VectorsCombiner, SanityChecker slice, the selected model's forward — traces
into its own jitted program: one compile per segment (cached across calls),
one host→device transfer of each segment's frontier columns, one
device→host transfer of the requested results per ``score()`` call
(SURVEY.md §2.6 P5: HBM residency replaces ``.persist()``).  For a typical
numeric workflow that is ONE fused program; text-heavy DAGs get a device
segment before and after their string stages.

Stages over strings/objects join device segments through the STAGED
protocol (``Transformer.transform_staged``): their host prologue runs
before the segment and contributes compact wire arrays (token ids, vocab
codes) to the frontier, and their traceable body runs inside the fused
program — so even a text-heavy vectorizer layer compiles into one XLA
program.  Stages with neither a device nor a staged form run eagerly
between the compiled segments.  A stage whose ``is_device_op``/staging flag
is optimistic but whose transform turns out not to be traceable is demoted
automatically (one retry, then it joins the host segments for the lifetime
of the program).
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import threading
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from .columns import Column, ColumnBatch
from .resilience import maybe_inject, record_failure
from .stages.base import ColumnWired, Transformer
from .telemetry import REGISTRY, span

_WIRE_SEP = "\x00"      # wire-entry names: "<uid>\x00<key>" — never a column

# process-wide count of fused-program TRACES (each one implies an XLA
# compile).  The serving layer's "no online recompile after warmup" guarantee
# is asserted against this: snapshot after warmup, require no growth under
# traffic.  Incremented inside traced() — that body only executes while jax
# is actually tracing, never on a jit cache hit.
_TRACE_COUNT = [0]

# threads whose traces are deliberately off the books: AOT export warms the
# ladder at save() time, and a save running concurrently with a serving
# engine (lifecycle retrain+promote, the hot-reload tests) must not land its
# warmup traces inside the engine's online-trace measurement window — the
# engine would blame itself and demote to the local fallback.  jax traces on
# the calling thread, so a thread-local flag attributes exactly the
# suppressing thread's traces and nothing else.
_TRACE_LOCAL = threading.local()


def trace_count() -> int:
    return _TRACE_COUNT[0]


@contextlib.contextmanager
def suppress_trace_count():
    """Traces on THIS thread don't count toward ``trace_count()`` while the
    context is open (save-time AOT export warmup — see aot.py)."""
    prev = getattr(_TRACE_LOCAL, "suppress", False)
    _TRACE_LOCAL.suppress = True
    try:
        yield
    finally:
        _TRACE_LOCAL.suppress = prev


def compile_attribution() -> Dict[str, Any]:
    """Traces vs actual backend compiles vs persistent-cache hits, in one
    snapshot.  A trace that ends in a cache hit costs milliseconds; one that
    reaches the backend compiler costs seconds — warmup asserts should
    compare against ``new_compiles`` (cache-aware), not ``traces``."""
    from .profiling import compile_seconds, compile_stats, new_compile_count
    return {"traces": trace_count(),
            "new_compiles": new_compile_count(),
            "compile_seconds": round(compile_seconds(), 4),
            **compile_stats()}


def _args_sig(arrays) -> Optional[str]:
    """Canonical JSON input-aval signature of one call's argument pytree —
    the VARIANT coordinate for per-(key, sig) AOT executables.  The program
    key carries only (stages, keep_intermediate, rows); sparse frontier
    columns add an nnz-capacity degree of freedom only the avals see."""
    try:
        import json

        from .aot_registry import args_signature
        return json.dumps(args_signature(arrays), sort_keys=True,
                          default=repr)
    except Exception:  # noqa: BLE001 — unsignable args are just unexported
        return None


class _SharedExecutables:
    """Bounded LRU of compiled executables (``jax.stages.Compiled``) by
    program identity (:func:`_program_identity`), shared by every
    ``ScoreProgram`` of the process.  Entries hold the executable only — no
    stage, batch or column metadata stays alive through them."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._lock = threading.Lock()   # the serving engine scores on threads
        self._entries: "collections.OrderedDict[str, Any]" = \
            collections.OrderedDict()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, ident: str) -> Optional[Any]:
        with self._lock:
            exe = self._entries.get(ident)
            if exe is not None:
                self._entries.move_to_end(ident)
            return exe

    def put(self, ident: str, exe: Any) -> Any:
        """Insert unless ``ident`` is held already (two threads that missed
        together both compile; the first insert wins and both dispatch it).
        Returns the executable held; evicts the least recently used past
        ``capacity``."""
        evicted = 0
        with self._lock:
            exe = self._entries.setdefault(ident, exe)
            self._entries.move_to_end(ident)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                evicted += 1
        if evicted:
            REGISTRY.counter("compiled.shared.evict").inc(evicted)
        return exe


# a train flushes three fused programs and a served model holds one per
# segment and padded batch size (7 sizes by default): room for a few models'
# worth, small enough that stale executables do not pile up on the device
SHARED_EXECUTABLES = _SharedExecutables(capacity=64)


def _program_identity(lowered) -> Optional[str]:
    """Digest of everything that decides which executable is right for
    ``lowered`` (a ``jax.stages.Lowered``), or None where that cannot be
    shown.  The lowered module's text carries the computation, the input
    avals, shardings and donation, and — as jax bakes closed-over values
    into the module — every fitted value ``traced`` closes over, constants
    printed in full; beside it go the platform, the ids of the devices it
    is lowered for (a mesh's, in assignment order) and the argument and
    result pytrees.  None when something outside the module would reach
    the executable: constants hoisted into hidden call arguments
    (``jax_use_simplified_jaxpr_constants``) or host callbacks."""
    try:
        low = lowered._lowering
        if (low.const_args or low.compile_args["host_callbacks"]
                or low.compile_args["keepalive"]):
            return None
        placement = (tuple(low._platforms),
                     tuple(int(d.id) for d in low._device_list))
    except (AttributeError, KeyError, TypeError):
        return None     # another jax's internals: no proof, no sharing
    h = hashlib.sha256(lowered.as_text().encode())
    h.update(repr((placement, str(lowered.in_tree),
                   str(lowered.out_tree))).encode())
    return h.hexdigest()


def _shared_executable(jitted, arrays) -> Tuple[Any, bool]:
    """What the first call of a fresh fused program dispatches to, and
    whether an earlier program of this process compiled it.  The stages are
    traced and the module lowered either way (the trace is what fills this
    program's output metadata; the module is the identity); only the
    backend compile — or its persistent-cache load — is skipped on a hit."""
    lowered = jitted.lower(arrays)
    with span("transform.program_key"):
        ident = _program_identity(lowered)
    if ident is None:
        # jit keeps the trace and the lowering just made; it compiles alone
        REGISTRY.counter("compiled.shared.bypass").inc()
        return jitted, False
    exe = SHARED_EXECUTABLES.get(ident)
    if exe is not None:
        REGISTRY.counter("compiled.shared.hit").inc()
        return exe, True
    REGISTRY.counter("compiled.shared.miss").inc()
    return SHARED_EXECUTABLES.put(ident, lowered.compile()), False


class _StageTraceError(Exception):
    """Tracing failed inside a specific stage; carries the stage uid."""

    def __init__(self, uid: str, cause: Exception):
        super().__init__(uid)
        self.uid = uid
        self.cause = cause


class ScoreProgram:
    """A fitted DAG compiled for repeated scoring.

    ``program = ScoreProgram(stages, result_names)`` then
    ``scored = program(batch)`` — equivalent to ``apply_dag`` but every
    maximal contiguous run of device-traceable (or staged) stages executes
    as one jitted XLA program (host stages eager in between).

    Where an executable lives.  ``_jitted[key]`` holds the ``jax.jit``
    wrapper of a segment at a row count (``key`` = stage uids,
    keep_intermediate, rows); the wrapper traces and lowers, and AOT export
    lowers and clears it.  What a call dispatches to is held beside it, in
    ``_executables``, per input-aval signature and mesh devices: the
    ``jax.stages.Compiled`` of the lowered module, taken from the
    process-wide ``SHARED_EXECUTABLES`` where an earlier ``ScoreProgram``
    of this process — another train's, a reloaded model's — compiled the
    same module, and compiled (or loaded from the persistent cache) and put
    there otherwise.  That table's key (:func:`_program_identity`) is a
    digest of the lowered module's text, which holds the computation, the
    avals, shardings and every fitted value the stages close over, plus the
    platform, the device ids and the argument and result pytrees; stage
    uids and column names are not in it, and the column metadata of a call
    always comes from this program's own trace.  An executable installed
    from a bundle or the fleet registry replaces the wrapper and is
    dispatched first.  So a fixed schema compiles each segment once a
    process, and traces and lowers it once a ``ScoreProgram``.
    """

    def __init__(self, dag: Sequence, result_names: Sequence[str]):
        # accept a layered DAG or a flat stage list; within a layer, order
        # host ops before device/staged ops (any within-layer order is
        # topologically legal) so device segments coalesce instead of
        # fragmenting
        layers = ([list(l) for l in dag]
                  if dag and isinstance(dag[0], (list, tuple)) else [list(dag)])
        self.stages: List[Transformer] = []
        for layer in layers:
            self.stages.extend(sorted(
                layer, key=lambda s: bool(s.is_device_op
                                          or s.supports_staging)))
        self.result_names = list(result_names)
        self._demoted: Set[str] = set()   # uids proven untraceable
        self._jitted: Dict[Tuple, Any] = {}
        self._metas: Dict[Tuple, Dict[str, Any]] = {}
        # (key, input-aval signature, mesh device ids) -> what its calls
        # dispatch to: a shared executable, or the jit wrapper itself where
        # sharing was refused (class docstring)
        self._executables: Dict[Tuple, Any] = {}
        # AOT seams (see aot.py): per-key input avals captured at first call
        # (what export lowers against), and keys whose entry is a
        # deserialized pre-compiled executable rather than a jit wrapper
        self._input_specs: Dict[Tuple, Any] = {}
        self._aot_installed: Set[Tuple] = set()
        # aval-variant seam (ISSUE 19): the program-table key carries only
        # (stage uids, keep_intermediate, rows) — sparse frontier columns
        # add an nnz-capacity degree of freedom the key cannot see.  Every
        # distinct input-aval signature observed per key records its specs
        # here (what export lowers against), and pre-compiled executables
        # for specific signatures install per (key, sig) so one padded row
        # rung serves the whole nnz ladder with zero traces.
        self._input_spec_variants: Dict[Tuple, Dict[str, Any]] = {}
        self._aot_variants: Dict[Tuple[Tuple, str], Tuple] = {}
        # (key, sig) pairs already offered to the fleet registry — a miss is
        # memoized so steady-state calls pay zero registry lookups
        self._registry_checked: Set[Tuple] = set()
        # model-content digest tying this program to the fleet registry
        # (aot_registry.py); set by workflow load/save, None = no registry
        self.registry_family: Optional[str] = None

    def install_executable(self, key: Tuple, fn: Any,
                           canon_out: Dict[str, str],
                           metas: Dict[str, Any],
                           sig: Optional[str] = None) -> None:
        """Install a deserialized AOT executable for ``key`` — subsequent
        calls at that exact (stages, rows) signature dispatch straight to it
        with zero traces and zero compiles.  A call-time failure (shape or
        ABI drift the stamp missed) uninstalls it and falls back to jit.

        With ``sig`` (an input-aval signature, see ``_args_sig``) the
        executable installs as a VARIANT for that exact signature only: the
        key's jit entry stays intact, so calls at other signatures (e.g.
        other sparse nnz capacities) still trace/compile correctly instead
        of crashing into a mis-shaped executable."""
        if sig is not None:
            self._aot_variants[(key, sig)] = (fn, dict(canon_out),
                                              dict(metas))
            return
        self._jitted[key] = (fn, dict(canon_out))
        self._metas[key] = dict(metas)
        self._aot_installed.add(key)

    def aot_installed_count(self) -> int:
        return len(self._aot_installed) + len(self._aot_variants)

    def _forget(self, key: Tuple) -> None:
        """Drop ``key``'s jit wrapper, metadata and held executables."""
        self._jitted.pop(key, None)
        self._metas.pop(key, None)
        for held in [k for k in self._executables if k[0] == key]:
            del self._executables[held]

    # -- partition ----------------------------------------------------------
    def _partition(self, batch: ColumnBatch) -> List[Tuple[bool, List[Transformer]]]:
        """Split stages (already in topo order) into alternating
        (is_device_segment, stages) groups: every maximal contiguous stretch
        of device ops over array-resident inputs — plus staged stages whose
        inputs are materialized before the segment — becomes its own jitted
        segment, with host stages eager in between."""
        arrayish: Dict[str, bool] = {
            name: batch[name].is_device for name in batch.names()}
        segments: List[Tuple[bool, List[Transformer]]] = []
        seg_outputs: Set[str] = set()   # outputs of the OPEN device segment
        for st in self.stages:
            dev_ok = (st.is_device_op and st.uid not in self._demoted
                      and all(arrayish.get(f.name, False)
                              for f in st.input_features))
            # a staged stage's host prologue runs BEFORE the segment, so its
            # inputs must not be produced inside the same segment
            staged_ok = (not dev_ok and st.supports_staging
                         and st.uid not in self._demoted
                         and not any(f.name in seg_outputs
                                     for f in st.input_features))
            ok = dev_ok or staged_ok
            for f in st.output_features:
                # host stages may still emit array columns (e.g. one-hot on
                # strings); simulate with the same rule Column.is_device uses
                arrayish[f.name] = True if ok else _kind_arrayish(f.kind)
            if segments and segments[-1][0] == ok:
                segments[-1][1].append(st)
            else:
                segments.append((ok, [st]))
                seg_outputs = set()
            if ok:
                seg_outputs.update(f.name for f in st.output_features)
        return segments

    # -- execution ----------------------------------------------------------
    def __call__(self, batch: ColumnBatch, keep_intermediate: bool = False
                 ) -> ColumnBatch:
        # stages run outside a compiled segment, input columns whose wire a
        # train's prologue pool made ahead (``ColumnWired``), values a pivot
        # handled one by one in Python (``ops.categorical``), and hashed
        # text columns whose counts the Pallas kernel or XLA's scatter built
        # (``ops.text``); created at 0 by a flush that counts none, so that
        # a reader can tell 0 from no counter
        host_stages = REGISTRY.counter("transform.host_stages")
        REGISTRY.counter("transform.wires_ahead")
        REGISTRY.counter("pivot.python_values")
        REGISTRY.counter("text.hash_kernel_columns")
        REGISTRY.counter("text.hash_scatter_columns")
        for _attempt in range(len(self.stages) + 1):
            segments = self._partition(batch)
            b = batch
            try:
                for i, (is_dev, stages) in enumerate(segments):
                    if not is_dev:
                        host_stages.inc(len(stages))
                        with span("transform.host_stage", stages=len(stages)):
                            for st in stages:
                                b = st.transform_batch(b)
                        continue
                    later = [st for _, seg in segments[i + 1:] for st in seg]
                    b = self._apply_run(b, stages, later, keep_intermediate)
            except _StageTraceError as e:
                # demote the offending stage to the host segments and
                # re-partition; transforms are pure so re-running the
                # prologue on the original batch is safe
                record_failure(e.uid, "demoted", e.cause,
                               point="compiled.trace",
                               fallback="host segment")
                self._demoted.add(e.uid)
                continue
            return b
        raise RuntimeError("ScoreProgram failed to converge on a partition")

    def _wanted_outputs(self, run: List[Transformer], later: List[Transformer],
                        keep_intermediate: bool) -> List[str]:
        produced = [f.name for st in run for f in st.output_features]
        if keep_intermediate:
            return produced
        needed = set(self.result_names)
        for st in later:
            needed.update(f.name for f in st.input_features)
        return [n for n in produced if n in needed]

    def _wire(self, batch: ColumnBatch, frontier: List[str],
              wires: Dict[str, Any], canon_in: Dict[str, str], key: Tuple,
              n_rows: int):
        """The call's arguments as the fused program takes them: frontier
        columns and wire arrays under their canonical names, float32 on the
        bf16 wire, row-sharded over the mesh when there is one.  Returns
        (arrays, their aval signature, the mesh or None)."""
        def _prep(v):
            # float32 columns ride the bf16 wire format to the device (see
            # columns.to_device_f32); other dtypes transfer as-is inside jit
            if isinstance(v, np.ndarray) and v.dtype == np.float32:
                from .columns import to_device_f32
                return to_device_f32(v)
            return v

        arrays = {canon_in[n]: (_prep(batch[n].values), batch[n].mask)
                  for n in frontier}
        arrays.update({canon_in[k]: (_prep(v), None)
                       for k, v in wires.items()})
        sig = _args_sig(arrays)
        if key not in self._input_specs:
            try:
                # unsharded host-side avals — what AOT export lowers against
                self._input_specs[key] = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), arrays)
            except Exception:  # noqa: BLE001 — a non-array wire entry just
                pass           # makes this key non-exportable
        if sig is not None and sig not in self._input_spec_variants.get(
                key, {}):
            try:
                # every observed aval signature keeps its own exportable
                # specs: sparse nnz capacities vary per call under one key
                self._input_spec_variants.setdefault(key, {})[sig] = \
                    jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        arrays)
            except Exception:  # noqa: BLE001 — unexportable variant
                pass
        # host-resident wire args copy to the device inside the jit call (or
        # in the sharding block below); count them toward the phase's link
        # bytes BEFORE _shard turns them into jax Arrays
        from .profiling import add_host_link_bytes
        add_host_link_bytes(sum(
            a.nbytes for v, m in arrays.values() for a in (v, m)
            if isinstance(a, np.ndarray)))
        # multi-device: row-shard every per-row input over the mesh 'data'
        # axis — the fused program then runs as one GSPMD computation
        # (SURVEY §2.6 P1 on the scoring path; ≙ applyOpTransformations'
        # executor row map, FitStagesUtil.scala:96).  Non-row wires (packed
        # token words, per-row+1 lens) stay replicated.
        from .parallel.mesh import data_sharding, maybe_data_mesh
        mesh = maybe_data_mesh(n_rows)
        if mesh is not None:
            try:
                def _shard(x):
                    if (x is not None and getattr(x, "ndim", 0) >= 1
                            and x.shape[0] == n_rows):
                        return jax.device_put(x, data_sharding(mesh, x.ndim))
                    return x
                arrays = {k: (_shard(v), _shard(m))
                          for k, (v, m) in arrays.items()}
            except Exception as e:  # noqa: BLE001 — sharding is an
                # optimization; a failed reshard (e.g. RESOURCE_EXHAUSTED
                # near capacity) must fall back to the unsharded program,
                # never break scoring
                record_failure("compiled", "degraded", e,
                               point="compiled.shard",
                               fallback="unsharded program")
        return arrays, sig, mesh

    def _apply_run(self, batch: ColumnBatch, run: List[Transformer],
                   later: List[Transformer], keep_intermediate: bool
                   ) -> ColumnBatch:
        # staged = stages whose inputs are NOT all array-resident right now;
        # their host prologue supplies wire arrays instead of columns
        staged_fns: Dict[str, Any] = {}
        wires: Dict[str, Any] = {}
        with span("transform.stage_wires", stages=len(run)):
            for st in run:
                # a device op reads its columns inside the program; a staged
                # stage (``_partition`` put it here for its staged form)
                # always goes through its prologue, arrays or not: an int64
                # date is an array and still has no place on the chip
                if st.is_device_op and all(
                        batch[f.name].is_device for f in st.input_features
                        if f.name in batch):
                    continue
                res = None
                try:
                    # a wire the train's prologue pool made ahead, column by
                    # column (``ColumnWired.start_wires``): joined here, its
                    # work in the workers' spans; else made here
                    parts = (st.take_wires(batch)
                             if isinstance(st, ColumnWired) else None)
                    if parts is not None:
                        res = st.transform_staged(batch, parts)
                    else:
                        with span("transform.stage_wires."
                                  + type(st).__name__, rows=len(batch),
                                  columns=len(st.input_features)) as sp:
                            res = st.transform_staged(batch)
                            if sp is not None and res is not None:
                                sp.attrs["wire_bytes"] = int(sum(
                                    getattr(v, "nbytes", 0)
                                    for v in res[0].values()))
                except Exception as e:  # noqa: BLE001 — demotion signal
                    raise _StageTraceError(st.uid, e) from e
                if res is None:
                    raise _StageTraceError(st.uid, TypeError(
                        "stage has host inputs and no staged form"))
                wire, fn = res
                staged_fns[st.uid] = fn
                for k, v in wire.items():
                    wires[st.uid + _WIRE_SEP + k] = v

        key = (tuple(st.uid for st in run), keep_intermediate, len(batch))
        frontier = sorted({f.name for st in run
                           if st.uid not in staged_fns
                           for f in st.input_features if f.name in batch})
        # canonical positional names at the jit boundary: stage uids are
        # process-global counters, so real column/wire names differ between
        # otherwise identical workflows — with them as pytree keys every new
        # process MISSES the persistent compilation cache and pays a full
        # XLA recompile of the fused program
        canon_in = {n: f"a{i}" for i, n in enumerate(
            frontier + sorted(wires))}
        # _partition simulates host-stage outputs by kind; validate against
        # the actual columns and demote consumers of any misprediction (e.g.
        # a numeric-kinded host stage that emitted an object array)
        host_cols = [n for n in frontier if not batch[n].is_device]
        if host_cols:
            offender = next(st for st in run if st.uid not in staged_fns
                            and any(f.name in host_cols
                                    for f in st.input_features))
            raise _StageTraceError(offender.uid, TypeError(
                f"frontier columns {host_cols} are host-resident"))
        out_names = self._wanted_outputs(run, later, keep_intermediate)
        kinds = {n: batch[n].kind for n in frontier}
        metas_in = {n: batch[n].meta for n in frontier}
        n_rows_static = len(batch)

        fresh = key not in self._jitted
        if fresh:
            metas_out: Dict[str, Any] = {}
            fns_at_trace = dict(staged_fns)
            inv_in = {c: n for n, c in canon_in.items()}
            canon_out = {n: f"o{i}" for i, n in enumerate(out_names)}

            def traced(arrays_c: Dict[str, Tuple[Any, Any]]):
                if not getattr(_TRACE_LOCAL, "suppress", False):
                    _TRACE_COUNT[0] += 1
                arrays = {inv_in[c]: vm for c, vm in arrays_c.items()}
                cols = {n: Column(kinds[n], v, m, meta=metas_in[n])
                        for n, (v, m) in arrays.items()
                        if _WIRE_SEP not in n}
                b = ColumnBatch(dict(cols), n_rows_static)
                for st in run:
                    try:
                        # the stage's operations carry its class and output
                        # kind in the device trace (a uid is a process
                        # counter and would not repeat)
                        with jax.named_scope(_stage_scope(st)):
                            if st.uid in fns_at_trace:
                                sub = {k.split(_WIRE_SEP, 1)[1]: v
                                       for k, (v, _) in arrays.items()
                                       if k.startswith(st.uid + _WIRE_SEP)}
                                out_col = fns_at_trace[st.uid](sub)
                                (f,) = st.output_features
                                b = b.with_columns({f.name: out_col})
                            else:
                                b = st.transform_batch(b)
                    except _StageTraceError:
                        raise
                    except Exception as e:  # noqa: BLE001 — demotion signal
                        raise _StageTraceError(st.uid, e) from e
                out = {}
                for n in out_names:
                    c = b[n]
                    metas_out[n] = (c.meta, c.kind)
                    out[canon_out[n]] = (c.values, c.mask)
                return out

            self._jitted[key] = (jax.jit(traced), canon_out)
            self._metas[key] = metas_out

        with span("transform.wire", arrays=len(frontier) + len(wires)):
            arrays, sig, mesh = self._wire(batch, frontier, wires, canon_in,
                                           key, n_rows_static)
        if (mesh is None and key not in self._aot_installed
                and (key, sig) not in self._aot_variants
                and (key, sig) not in self._registry_checked):
            # fleet-registry seam: a published executable for this exact
            # (family, stages, rows, avals) installs over the untraced jit
            # entry (or as an aval variant when the signature is known) —
            # the dispatch below then runs with zero compiles.  Misses are
            # memoized per (key, sig) so steady-state traffic pays zero
            # registry lookups.
            self._registry_checked.add((key, sig))
            from .aot_registry import try_install_score
            try_install_score(self, key, arrays, sig=sig)
        if mesh is None and sig is not None:
            var = self._aot_variants.get((key, sig))
            if var is not None:
                # variant fast path: a pre-compiled executable for this
                # exact aval signature — zero traces, zero compiles, own
                # metas; the key's jit entry stays warm as the fallback
                vfn, v_canon_out, v_metas = var
                try:
                    maybe_inject("compiled.segment", key=run[0].uid)
                    with span("transform.dispatch", installed=True):
                        out_c = vfn(arrays)
                    out = {n: out_c[c] for n, c in v_canon_out.items()}
                    new_cols = {}
                    for n, (v, m) in out.items():
                        meta, kind = v_metas[n]
                        new_cols[n] = Column(kind, v, m, meta=meta)
                    return batch.with_columns(new_cols)
                except Exception as e:  # noqa: BLE001 — variants are an
                    # optimization: a rejected dispatch (aval drift the sig
                    # missed) falls through to the ordinary jit path below
                    record_failure("compiled", "degraded", e,
                                   point="compiled.aot",
                                   fallback="JIT recompile")
                    REGISTRY.counter("aot.fallback").inc()
                    self._aot_variants.pop((key, sig), None)
        jitted, canon_out_map = self._jitted[key]
        # an installed executable wins over the shared table; arguments with
        # no signature stay with jit, which keys on the avals itself
        installed = key in self._aot_installed
        held = (key, sig, None if mesh is None
                else tuple(int(i) for i in mesh.device_ids.flat))
        call = (jitted if installed or sig is None
                else self._executables.get(held))
        try:
            # chaos hook: an injected fault here exercises the eager-segment
            # demotion below, the same path a device dispatch failure takes
            maybe_inject("compiled.segment", key=run[0].uid)
            # the first call of a program at these avals pays trace and
            # lowering, and a compile or a cache load unless the process
            # holds the executable already; a later call or an installed
            # executable dispatches only
            first = call is None or (fresh and not installed)
            with span("transform.first_call" if first
                      else "transform.dispatch") as sp:
                if call is None:
                    call, shared = _shared_executable(jitted, arrays)
                    self._executables[held] = call
                    if sp is not None:
                        sp.attrs["shared"] = shared
                out_c = call(arrays)
            out = {n: out_c[c] for n, c in canon_out_map.items()}
        except _StageTraceError:
            self._forget(key)
            raise
        except Exception as e:  # noqa: BLE001
            if key in self._aot_installed:
                # the shipped executable rejected these inputs (shape/dtype
                # drift the ABI stamp could not see) — uninstall it and
                # retry on the ordinary jit path instead of going eager
                record_failure("compiled", "degraded", e,
                               point="compiled.aot",
                               fallback="JIT recompile")
                REGISTRY.counter("aot.fallback").inc()
                self._aot_installed.discard(key)
                self._forget(key)
                return self._apply_run(batch, run, later, keep_intermediate)
            # unexpected jit-boundary failure: never break scoring — run the
            # segment eagerly (≙ apply_dag) and stop attempting to compile
            record_failure("compiled", "demoted", e,
                           point="compiled.segment",
                           stages=[st.uid for st in run],
                           fallback="eager per-stage execution")
            self._forget(key)
            self._demoted.update(st.uid for st in run)
            REGISTRY.counter("transform.host_stages").inc(len(run))
            b = batch
            for st in run:
                b = st.transform_batch(b)
            return b
        metas_out = self._metas[key]
        new_cols = {}
        for n, (v, m) in out.items():
            meta, kind = metas_out[n]
            new_cols[n] = Column(kind, v, m, meta=meta)
        return batch.with_columns(new_cols)


def _stage_scope(st: Transformer) -> str:
    """``transform.<stage class>.<output kind>``: the ``jax.named_scope`` of
    a stage's operations inside the fused program."""
    out = st.output_features[0].kind if st.output_features else None
    return f"transform.{type(st).__name__}.{getattr(out, '__name__', out)}"


def _kind_arrayish(kind) -> bool:
    """Static analog of Column.is_device for a feature kind: does a column of
    this kind hold dense arrays (vs host object arrays)?"""
    from .types import Geolocation, OPVector, Prediction, is_numeric_kind
    if kind is None:
        return False
    if issubclass(kind, (OPVector, Prediction, Geolocation)):
        return True
    if is_numeric_kind(kind):
        return True
    return False
