"""AOT-serialized executables: kill the cold-start compile wall.

PR 4 made recompiles cheap-ish (persistent XLA compile cache); this module
makes the serve path skip the compiler entirely.  At ``model.save()`` the
fused transform+scoring programs are warmed across the serving padding
ladder, lowered, compiled, and serialized
(``jax.experimental.serialize_executable``) into a per-platform
subdirectory of the bundle (``aot-cpu/``, ``aot-tpu/``, ...).  Every
artifact is digest-covered by the bundle MANIFEST, so corruption surfaces
as ``CorruptModelError`` before a byte of it reaches the runtime.  On
``WorkflowModel.load`` the executables deserialize straight into the
``ScoreProgram`` jit table — a fresh process scores its first record with
zero XLA compiles (asserted by ``scripts/ci_aot_smoke.py``).

Safety: XLA CPU executables bake in host ISA features (the SIGILL hazard
noted in ``__init__.py``) and TPU executables bake in the chip generation,
so every artifact carries an ABI stamp (platform, machine, jax version,
device count).  A mismatched stamp, an undeserializable payload, or a
shape/dtype drift at call time all fall back to the ordinary JIT path with
a ``degraded`` FailureLog note — AOT is an optimization, never a
correctness dependency.  Opt out with ``--no-aot`` / ``aotParams`` /
``TRANSMOGRIFAI_NO_AOT=1``.

The train-side half lives here too: ``pretrace_submit`` runs a family's
grid program through ``lower().compile()`` on a background thread while
transmogrification / fold prep still owns the main thread.  The compile
lands in the persistent cache, so the sweep's real fit call becomes a disk
hit and ``new_compiles_during_train`` collapses into otherwise-idle wall
time.  Estimators opt in via ``supports_pretrace`` (see models/base.py);
inside the pretrace scope their ``fit_arrays_grid`` only lowers+compiles —
it never executes, so sweep winners are bitwise unaffected.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import pickle
import platform as _platform
import threading
from typing import Any, Dict, List, Optional, Tuple

AOT_FORMAT_VERSION = 1
AOT_DIR_PREFIX = "aot-"
AOT_META_NAME = "aot.json"

# default ladder ceiling warmed/exported at save time; mirrors
# ScoringEngine's default max_batch so a default engine serves every
# padded batch size from shipped executables
_DEFAULT_LADDER_MAX = 64

_DISABLED = [False]          # process-level kill switch (--no-aot / params)


def set_aot_enabled(on: bool) -> None:
    _DISABLED[0] = not on


def aot_enabled() -> bool:
    if _DISABLED[0]:
        return False
    return os.environ.get("TRANSMOGRIFAI_NO_AOT", "0") in ("", "0")


def _count(name: str, n: int = 1) -> None:
    from .telemetry import REGISTRY
    REGISTRY.counter(name).inc(n)


# -- ABI stamp ---------------------------------------------------------------

def abi_stamp() -> Dict[str, Any]:
    """The compiling environment an executable is only valid in: XLA CPU
    payloads bake in host machine features, TPU payloads the chip
    generation, and jax pins the serialization format to its own version."""
    import jax
    return {
        "platform": jax.default_backend(),
        "machine": _platform.machine(),
        "jaxVersion": jax.__version__,
        "deviceCount": jax.device_count(),
    }


def abi_mismatch(stamp: Optional[Dict[str, Any]]) -> Optional[str]:
    """None when ``stamp`` matches the running process, else a short reason
    string naming the first mismatched field."""
    if not isinstance(stamp, dict):
        return "missing ABI stamp"
    here = abi_stamp()
    for field in ("platform", "machine", "jaxVersion", "deviceCount"):
        if stamp.get(field) != here[field]:
            return (f"{field} mismatch: bundle={stamp.get(field)!r} "
                    f"host={here[field]!r}")
    return None


# -- bundle export (save side) ----------------------------------------------

def _key_json(key: Tuple) -> Dict[str, Any]:
    uids, keep_intermediate, rows = key
    return {"uids": list(uids), "keepIntermediate": bool(keep_intermediate),
            "rows": int(rows)}


def _key_tuple(d: Dict[str, Any]) -> Tuple:
    return (tuple(d["uids"]), bool(d["keepIntermediate"]), int(d["rows"]))


def ladder_sizes(max_batch: int = _DEFAULT_LADDER_MAX) -> List[int]:
    from .serving.engine import _padding_ladder
    return _padding_ladder(max_batch)


def export_bundle(model, bundle_dir: str) -> int:
    """Warm ``model``'s score program across the serving padding ladder and
    serialize the resulting executables under
    ``<bundle_dir>/aot-<platform>/``.  Returns the number of executables
    written (0 disables nothing — a bundle without AOT artifacts simply
    loads on the JIT path).  Raises nothing: any failure is recorded as a
    swallowed FailureLog entry and the bundle ships without AOT."""
    from .resilience import record_failure
    if not aot_enabled():
        return 0
    try:
        return _export_bundle_inner(model, bundle_dir)
    except Exception as e:  # noqa: BLE001 — AOT is strictly optional
        record_failure("workflow.save", "swallowed", e,
                       point="checkpoint.aot",
                       detail="AOT export failed; bundle ships JIT-only")
        return 0


def _export_bundle_inner(model, bundle_dir: str) -> int:
    import jax
    from .resilience import record_failure
    from .serving.engine import records_to_batch
    from .telemetry import span

    program = model.score_program()
    max_batch = int(os.environ.get("TRANSMOGRIFAI_AOT_LADDER_MAX",
                                   _DEFAULT_LADDER_MAX))
    sizes = ladder_sizes(max_batch)
    from . import aot_registry
    from .compiled import suppress_trace_count
    # every trace in here — the ladder warm-up AND a rebuild's re-trace —
    # stays off the global trace_count() books: a save() running
    # concurrently with a serving engine (lifecycle retrain+promote) must not
    # land export traces inside the engine's online-trace window.  Where
    # only an executable BUILT in this process serializes into something
    # that runs (XLA:CPU, aot_registry.cache_loads_reserialize) the
    # persistent compile cache is suspended throughout, so what the warm-up
    # compiles is a real build
    fresh_only = not aot_registry.cache_loads_reserialize()
    with span("workflow.aot_export", sizes=sizes), suppress_trace_count(), \
            (aot_registry.persistent_cache_suspended() if fresh_only
             else contextlib.nullcontext()):
        # warm: score a synthetic record at every ladder size so the program
        # table holds exactly the serve-shaped entries (same monoid-zero
        # record ScoringEngine warms with)
        before = set(program._jitted)
        for size in sizes:
            try:
                batch = records_to_batch(model.raw_features, [{}] * size)
                model.score(batch=batch)
            except Exception as e:  # noqa: BLE001 — skip unwarmable sizes
                record_failure("workflow.save", "swallowed", e,
                               point="checkpoint.aot",
                               detail=f"AOT warm at batch size {size}")
        # nnz-ladder warm (ISSUE 19): a sparse (hashed-text) frontier
        # column's flat-component shape is its nnz CAPACITY — the
        # monoid-zero records above only exercise the floor rung
        # (nnz=0 → cap 1024, which already serves every real batch with
        # ≤1024 entries).  Synthetic token records push the program
        # across higher nnz rungs so those serve with zero compiles
        # too.  Densities are tokens/record
        # (TRANSMOGRIFAI_AOT_NNZ_LADDER, comma-separated, "" disables);
        # models without text features skip — same records, same avals,
        # no new table entries.
        from .types import is_text_kind
        text_feats = [f for f in model.raw_features
                      if f.kind is not None and is_text_kind(f.kind)]
        densities = []
        for tok in os.environ.get("TRANSMOGRIFAI_AOT_NNZ_LADDER",
                                  "32").split(","):
            with contextlib.suppress(ValueError):
                if int(tok) > 0:
                    densities.append(int(tok))
        for k_tok in densities if text_feats else []:
            text = " ".join(f"tok{j}" for j in range(k_tok))
            for size in sizes:
                try:
                    recs = [{f.name: text for f in text_feats}
                            for _ in range(size)]
                    batch = records_to_batch(model.raw_features, recs)
                    model.score(batch=batch)
                except Exception as e:  # noqa: BLE001
                    record_failure("workflow.save", "swallowed", e,
                                   point="checkpoint.aot",
                                   detail=f"AOT nnz warm at batch size "
                                          f"{size} x {k_tok} tokens")
        keys = [k for k in program._jitted
                if k in program._input_specs
                and (k in before or k[2] in sizes)]
        if not keys:
            return 0

        out_dir = os.path.join(bundle_dir,
                               AOT_DIR_PREFIX + jax.default_backend())
        os.makedirs(out_dir, exist_ok=True)
        index: List[Dict[str, Any]] = []
        written = 0
        pretrace_drain()
        # registry publish rides the same export loop: every executable the
        # bundle ships also lands in the fleet registry under its
        # family x rung key, so pool workers / tenants / CI on OTHER
        # bundles of the same content install instead of compiling
        family = (aot_registry.model_family_digest(bundle_dir)
                  if aot_registry.registry_enabled() else None)
        for i, key in enumerate(sorted(keys,
                                       key=lambda k: (k[2], k[0]))):
            # aval variants (ISSUE 19): a key that saw more than one
            # input signature (sparse nnz rungs) exports one record per
            # signature; single-variant keys export the legacy record —
            # byte-compatible with pre-variant bundles
            variants = program._input_spec_variants.get(key) or {}
            if len(variants) > 1:
                jobs = sorted(variants.items())
            else:
                jobs = [(None, None)]
            for j, (sig, specs) in enumerate(jobs):
                try:
                    if fresh_only and key in before and j == 0:
                        # first dispatched BEFORE this export: its memoized
                        # executable may be a cache load — drop it, the
                        # lower().compile() below builds it again
                        program._jitted[key][0].clear_cache()
                    rec = _serialize_key(program, key, specs=specs, sig=sig)
                except Exception as e:  # noqa: BLE001 — best effort
                    record_failure("workflow.save", "swallowed", e,
                                   point="checkpoint.aot",
                                   detail=f"AOT serialize "
                                          f"rows={key[2]}")
                    continue
                fname = (f"seg-{i:03d}.aotx" if sig is None
                         else f"seg-{i:03d}-v{j:02d}.aotx")
                with open(os.path.join(out_dir, fname), "wb") as f:
                    f.write(rec)
                ent = {"file": fname, **_key_json(key)}
                if sig is not None:
                    ent["argSig"] = sig
                index.append(ent)
                written += 1
                if family:
                    aot_registry.publish_score(family, key, program,
                                               rec, specs=specs)
        if family:
            program.registry_family = family
        if not written:
            # nothing serialized — drop the empty dir so the bundle stays
            # byte-identical to a JIT-only save
            with contextlib.suppress(OSError):
                os.rmdir(out_dir)
            return 0
        meta = {"formatVersion": AOT_FORMAT_VERSION, "abi": abi_stamp(),
                "executables": index}
        with open(os.path.join(out_dir, AOT_META_NAME), "w") as f:
            json.dump(meta, f, indent=2, sort_keys=True)
        _count("aot.executables_saved", written)
        return written


def _serialize_key(program, key: Tuple, specs: Any = None,
                   sig: Optional[str] = None) -> bytes:
    """Lower+compile+serialize one program-table entry.  ``specs``/``sig``
    select an aval VARIANT (ISSUE 19): sparse frontier columns put an
    nnz-capacity degree of freedom in the avals that the 3-field key cannot
    see, so multi-variant keys export one record per observed signature
    (tagged ``argSig``); single-variant keys stay byte-compatible with
    pre-variant bundles."""
    from jax.experimental.serialize_executable import serialize

    from . import aot_registry
    jitted, canon_out = program._jitted[key]
    if specs is None:
        specs = program._input_specs[key]
    compiled = jitted.lower(specs).compile()
    payload, in_tree, out_tree = serialize(compiled)
    rec = {
        "key": _key_json(key),
        "canonOut": dict(canon_out),
        "metas": dict(program._metas.get(key, {})),
        "payload": payload,
        "inTree": in_tree,
        "outTree": out_tree,
        "deviceIds": aot_registry.executable_device_ids(compiled),
    }
    if sig is not None:
        rec["argSig"] = sig
    buf = io.BytesIO()
    pickle.dump(rec, buf, protocol=4)
    return buf.getvalue()


# -- bundle install (load side) ----------------------------------------------

def install_bundle(model, bundle_path: str) -> int:
    """Deserialize the bundle's AOT executables (if any, for this platform)
    into ``model``'s score program.  Returns the number installed.  Any
    mismatch or failure records a ``degraded`` note and leaves the model on
    the ordinary JIT path — never raises."""
    import glob

    from .resilience import record_failure
    if not aot_enabled():
        return 0

    def _fallback(reason: str, cause: Any = None) -> int:
        _count("aot.fallback")
        record_failure("checkpoint", "degraded",
                       cause if isinstance(cause, Exception) else reason,
                       point="checkpoint.aot", bundle=bundle_path,
                       fallback="JIT scoring path", detail=reason)
        return 0

    import jax
    here = AOT_DIR_PREFIX + jax.default_backend()
    aot_dir = os.path.join(bundle_path, here)
    if not os.path.isdir(aot_dir):
        others = [os.path.basename(d) for d in
                  glob.glob(os.path.join(bundle_path, AOT_DIR_PREFIX + "*"))
                  if os.path.isdir(d)]
        if others:
            return _fallback(
                f"bundle has AOT artifacts for {others}, none for {here}")
        return 0    # legacy / JIT-only bundle: nothing to do, nothing to log

    try:
        with open(os.path.join(aot_dir, AOT_META_NAME)) as f:
            meta = json.load(f)
    except Exception as e:  # noqa: BLE001
        return _fallback("unreadable aot.json", e)
    if meta.get("formatVersion", 0) > AOT_FORMAT_VERSION:
        return _fallback(
            f"AOT formatVersion {meta.get('formatVersion')} is newer than "
            f"supported {AOT_FORMAT_VERSION}")
    reason = abi_mismatch(meta.get("abi"))
    if reason is not None:
        return _fallback(f"ABI {reason}")

    import hashlib

    from .aot_registry import shared_load
    program = model.score_program()
    installed = 0
    for ent in meta.get("executables", []):
        fpath = os.path.join(aot_dir, ent.get("file", ""))
        try:
            with open(fpath, "rb") as f:
                raw = f.read()
            rec = pickle.loads(raw)
            # deserialize through the process-wide shared table keyed on
            # content: two tenants loading byte-identical bundles (same
            # family x rung) get ONE loaded executable and one copy of its
            # device memory
            fn = shared_load(hashlib.sha256(raw).hexdigest(), rec)
            program.install_executable(_key_tuple(rec["key"]), fn,
                                       rec["canonOut"], rec["metas"],
                                       sig=rec.get("argSig"))
            installed += 1
        except Exception as e:  # noqa: BLE001
            _fallback(f"undeserializable executable "
                      f"{ent.get('file')}", e)
    if installed:
        _count("aot.executables_loaded", installed)
    return installed


# -- concurrent pre-trace (train side) ---------------------------------------

_PRETRACE_TLS = threading.local()


def pretrace_mode() -> bool:
    """True on threads currently inside :func:`pretrace_scope` — estimator
    ``fit_arrays_grid`` implementations branch on this to lower+compile
    their grid programs without executing them."""
    return bool(getattr(_PRETRACE_TLS, "on", False))


@contextlib.contextmanager
def pretrace_scope():
    prev = getattr(_PRETRACE_TLS, "on", False)
    _PRETRACE_TLS.on = True
    try:
        yield
    finally:
        _PRETRACE_TLS.on = prev


# one background DAEMON thread: pre-traces queue behind each other (XLA's
# compiler is internally parallel; a single worker avoids oversubscribing
# the host while transmogrification / fold prep still owns the main
# thread), and a daemon never blocks interpreter exit on a slow compile
_POOL_LOCK = threading.Lock()
_QUEUE: "queue.Queue" = None  # type: ignore[assignment]
_IDLE = threading.Event()
_IDLE.set()


def pretrace_enabled() -> bool:
    """Pre-tracing pays a background compile so the foreground fit becomes a
    persistent-cache hit — without the cache it would literally double the
    compile bill, so it keys on the env that places the cache.
    A configured executable registry also qualifies: its pre-trace pass can
    skip the compile entirely (deserialize a published executable) and its
    misses publish for the whole fleet."""
    if not aot_enabled():
        return False
    cache = os.environ.get("TRANSMOGRIFAI_COMPILE_CACHE", "")
    if bool(cache) and cache != "0":
        return True
    from .aot_registry import registry_enabled
    return registry_enabled()


def _pretrace_worker() -> None:
    from .resilience import record_failure
    while True:
        label, fn, failure_log = _QUEUE.get()
        try:
            try:
                with pretrace_scope():
                    fn()
                _count("aot.pretrace_compiled")
            except Exception as e:  # noqa: BLE001 — strictly advisory work
                _count("aot.pretrace_failed")
                # record into the SUBMITTER's log: the ambient thread-local
                # log does not cross into this worker thread
                if failure_log is not None:
                    failure_log.record("tuning", "swallowed", e,
                                       point="tuning.pretrace", detail=label)
                else:
                    record_failure("tuning", "swallowed", e,
                                   point="tuning.pretrace", detail=label)
        finally:
            _QUEUE.task_done()
            if _QUEUE.unfinished_tasks == 0:
                _IDLE.set()


def pretrace_submit(label: str, fn) -> None:
    """Run ``fn()`` (typically ``estimator.pretrace_arrays_grid(...)``) on
    the background pre-trace thread.  Failures are swallowed and counted —
    a missed pre-trace only costs the foreground compile it would have
    hidden."""
    global _QUEUE
    import queue

    from .resilience import active_failure_log
    with _POOL_LOCK:
        if _QUEUE is None:
            _QUEUE = queue.Queue()
            threading.Thread(target=_pretrace_worker, name="op-pretrace",
                             daemon=True).start()
        _count("aot.pretrace_submitted")
        _IDLE.clear()
        try:
            log = active_failure_log()
        except Exception:  # noqa: BLE001
            log = None
        _QUEUE.put((label, fn, log))


def pretrace_drain(timeout: Optional[float] = None) -> None:
    """Block until submitted pre-traces finish (tests / shutdown hygiene)."""
    _IDLE.wait(timeout)


def pretrace_shed() -> int:
    """Drop every QUEUED (not-yet-started) pre-trace — the RSS watchdog's
    soft-watermark shedder.  Pre-traces are strictly advisory (a dropped one
    only costs the foreground compile it would have hidden), so under host
    memory pressure they are the first load to go.  Returns the number of
    entries dropped (the watchdog logs it; exact bytes are unknowable before
    the compile runs)."""
    import queue

    with _POOL_LOCK:
        if _QUEUE is None:
            return 0
        dropped = 0
        while True:
            try:
                _QUEUE.get_nowait()
            except queue.Empty:
                break
            _QUEUE.task_done()
            dropped += 1
        if _QUEUE.unfinished_tasks == 0:
            _IDLE.set()
    if dropped:
        _count("aot.pretrace_shed", dropped)
    return dropped
