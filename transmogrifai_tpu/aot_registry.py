"""Fleet-wide content-addressed compiled-program registry: cold ≈ warm.

Serving cold-start is solved (aot.py ships executables inside each bundle),
but every OTHER first run still pays the compile wall in full: a fresh
process runs ~100 XLA compiles for the six-candidate dense sweep (count from
a CPU run; compile TIME on the chip is in PERF.md), and every pool worker,
tenant activation,
hostgroup rank, and lifecycle retrain re-derives the same executables.  The
programs themselves are already canonicalized — positional pytree names
at the jit boundary — so their identities are
stable across processes and machines with the same ABI.

This module is the registry those identities key into: a content-addressed,
on-disk table of serialized XLA executables under
``<root>/<platform>/<digest[:2]>/<digest>/`` where the digest covers

    kind (grid | score) x family x ladder-rung x canonicalized program
    signature (static config + input avals) x ``aot.abi_stamp()`` x a
    digest of the package source

so a stale entry can never be *found*, only evicted.  Every entry is a
directory written temp+fsync+rename (checkpoint.py conventions): two
processes racing to publish the same key converge on one valid entry, and a
reader never observes a torn payload.  Install verifies the payload's
SHA-256 against the entry metadata and the ABI stamp against the running
process; any mismatch degrades to the ordinary JIT path with a FailureLog
note — exactly the semantics already tested for serving AOT.  The registry
is an optimization, never a correctness dependency.

Three seams feed and drain it:

* **Train** — ``grid_call`` wraps every batched grid-fit dispatch
  (models/linear.py, models/trees.py): registry hit → the deserialized
  executable runs with ZERO traces and ZERO compiles; miss → the ordinary
  jit dispatch runs and a background publish serializes a fresh compile of
  the same program.  ``grid_compile`` is the compile-only twin the
  background pre-trace uses.
* **Serve** — ``compiled.ScoreProgram`` asks the registry before tracing a
  fused scoring program (key includes the model-content family digest), and
  ``aot.export_bundle`` publishes every executable it ships in a bundle —
  so an N-worker pool on a registry-warm machine boots with ≤1 compile
  total even when the bundle itself carries no AOT artifacts.
* **Tenants** — deserialized executables are memoized process-wide by
  payload digest (``shared_load``), so two tenants serving the same
  family x rung share ONE loaded executable and its device memory.

The registry never moves the persistent XLA compile cache: that directory
is decided once at package import (``JAX_COMPILATION_CACHE_DIR``, else
``TRANSMOGRIFAI_COMPILE_CACHE``, else the fixed in-checkout default — see
``transmogrifai_tpu/__init__.py``) and children inherit it through the
environment.  Both stores are size-capped: ``enforce_budget`` /
``gc_compile_cache`` run LRU-by-atime eviction under a byte budget,
stale-ABI entries first, with ``evicted`` FailureLog notes.

Opt out with ``--no-registry`` / ``registryParams`` /
``TRANSMOGRIFAI_AOT_REGISTRY=0``.
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import io
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

REGISTRY_FORMAT_VERSION = 1
ENTRY_META_NAME = "entry.json"
ENTRY_PAYLOAD_NAME = "payload.bin"

REGISTRY_ENV = "TRANSMOGRIFAI_AOT_REGISTRY"
CAP_ENV = "TRANSMOGRIFAI_AOT_REGISTRY_CAP_BYTES"
KEEP_ENV = "TRANSMOGRIFAI_AOT_REGISTRY_KEEP_MIN"
CACHE_CAP_ENV = "TRANSMOGRIFAI_COMPILE_CACHE_CAP_BYTES"

# default byte budgets: generous for a fleet cache, small enough that a
# long-lived checkpoint dir never grows without bound
DEFAULT_CAP_BYTES = 2 << 30          # registry entries
DEFAULT_CACHE_CAP_BYTES = 2 << 30    # persistent XLA compile cache
DEFAULT_KEEP_MIN = 8                 # newest entries never evicted

_LOCK = threading.RLock()
_STATE: Dict[str, Any] = {
    "enabled": True,        # kill switch (--no-registry / registryParams)
    "root": None,           # explicit root (params/cli); None = env/default
    "cap_bytes": None,
    "keep_min": None,
    "cache_cap_bytes": None,
}

# process-wide loaded-executable table: payload/key digest -> deserialized
# executable.  THE tenant-sharing seam — two engines installing the same
# payload get the same object (and its device allocations) back.
_LOADED: Dict[str, Any] = {}

# keys whose publish is already queued/done this process (dedup)
_PUBLISHED: set = set()

# grid key -> names of DYNAMIC keyword args the executable was lowered
# with (e.g. linear_grid_fit's traced ``tol``): a deserialized executable
# must be called with exactly the pytree it was lowered from, so these
# ride in each published record and are replayed at call time
_DYN_KWARGS: Dict[str, Tuple[str, ...]] = {}


def _count(name: str, n: int = 1) -> None:
    from .telemetry import REGISTRY
    REGISTRY.counter(name).inc(n)


# -- configuration -----------------------------------------------------------

def set_registry_enabled(on: bool) -> None:
    with _LOCK:
        _STATE["enabled"] = bool(on)


def registry_allowed() -> bool:
    """No kill switch thrown: params/CLI haven't disabled the registry, the
    env hasn't, and AOT itself is on.  (Whether a ROOT is configured is
    :func:`registry_enabled`'s business — callers that are about to default
    a root check this one.)"""
    from .aot import aot_enabled
    with _LOCK:
        if not _STATE["enabled"]:
            return False
    if not aot_enabled():
        return False
    return os.environ.get(REGISTRY_ENV, "") not in ("0", "off")


def registry_enabled() -> bool:
    """True when the registry may be consulted: not killed, and a root is
    known."""
    return registry_allowed() and registry_root() is not None


def registry_root() -> Optional[str]:
    """The registry directory, or None when unconfigured.  Order: explicit
    ``configure(root=...)`` (params/CLI) then the ``TRANSMOGRIFAI_AOT_-
    REGISTRY`` env var (also how pool workers / hostgroup ranks inherit the
    parent's root)."""
    with _LOCK:
        if _STATE["root"]:
            return _STATE["root"]
    env = os.environ.get(REGISTRY_ENV, "")
    if env and env not in ("0", "off", "1"):
        return env
    return None


def configure(root: Optional[str] = None, enabled: Optional[bool] = None,
              cap_bytes: Optional[int] = None,
              keep_min: Optional[int] = None,
              cache_cap_bytes: Optional[int] = None) -> None:
    """Apply registryParams / CLI flags.  Exports the root into the process
    environment so spawned children (serving pool workers, hostgroup ranks,
    supervised probes) install from the same registry without their own
    plumbing."""
    with _LOCK:
        if enabled is not None:
            _STATE["enabled"] = bool(enabled)
        if cap_bytes is not None:
            _STATE["cap_bytes"] = int(cap_bytes)
        if keep_min is not None:
            _STATE["keep_min"] = int(keep_min)
        if cache_cap_bytes is not None:
            _STATE["cache_cap_bytes"] = int(cache_cap_bytes)
        if root:
            _STATE["root"] = str(root)
            os.environ[REGISTRY_ENV] = str(root)
    if enabled is False:
        os.environ[REGISTRY_ENV] = "0"


def _cap_bytes() -> int:
    with _LOCK:
        if _STATE["cap_bytes"] is not None:
            return _STATE["cap_bytes"]
    try:
        return int(os.environ.get(CAP_ENV, DEFAULT_CAP_BYTES))
    except ValueError:
        return DEFAULT_CAP_BYTES


def _keep_min() -> int:
    with _LOCK:
        if _STATE["keep_min"] is not None:
            return _STATE["keep_min"]
    try:
        return int(os.environ.get(KEEP_ENV, DEFAULT_KEEP_MIN))
    except ValueError:
        return DEFAULT_KEEP_MIN


def _cache_cap_bytes() -> int:
    with _LOCK:
        if _STATE["cache_cap_bytes"] is not None:
            return _STATE["cache_cap_bytes"]
    try:
        return int(os.environ.get(CACHE_CAP_ENV, DEFAULT_CACHE_CAP_BYTES))
    except ValueError:
        return DEFAULT_CACHE_CAP_BYTES


def reset_for_tests() -> None:
    """Drop process-level state (loaded table, publish dedup, config) —
    test isolation only."""
    with _LOCK:
        _LOADED.clear()
        _PUBLISHED.clear()
        _DYN_KWARGS.clear()
        _STATE.update(enabled=True, root=None, cap_bytes=None,
                      keep_min=None, cache_cap_bytes=None)


# -- keys --------------------------------------------------------------------

_CODE_DIGEST: List[Optional[str]] = [None]


def code_digest() -> str:
    """SHA-256 over this package's source files (names + bytes).  Folded
    into every key: the signature scheme cannot see a code change that
    alters what a program COMPUTES at the same shapes, so any source drift
    invalidates the whole fleet's entries — conservative and safe."""
    if _CODE_DIGEST[0] is None:
        h = hashlib.sha256()
        pkg = os.path.dirname(os.path.abspath(__file__))
        for path in sorted(glob.glob(os.path.join(pkg, "**", "*.py"),
                                     recursive=True)):
            h.update(os.path.relpath(path, pkg).encode())
            try:
                with open(path, "rb") as fh:
                    h.update(fh.read())
            except OSError:
                h.update(b"?")
        _CODE_DIGEST[0] = h.hexdigest()[:16]
    return _CODE_DIGEST[0]


def _aval_sig(x: Any) -> Any:
    """Canonical JSON-able signature of one pytree leaf: (shape, dtype) for
    anything array-like, repr otherwise (static scalars riding in args)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return [list(int(d) for d in shape), str(dtype)]
    if x is None or isinstance(x, (bool, int, float, str)):
        return repr(x)
    return repr(type(x).__name__)


def args_signature(args: Any) -> List[Any]:
    """Flattened aval signature of a pytree of call arguments."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return [str(treedef)] + [_aval_sig(leaf) for leaf in leaves]


def program_key(kind: str, family: str, rung: int,
                statics: Optional[Dict[str, Any]],
                avals: Any) -> str:
    """The content address: every field that determines which executable is
    correct to run, hashed into one digest.  ``avals`` is anything
    JSON-serializable (usually ``args_signature(args)``)."""
    from .aot import abi_stamp
    doc = {
        "v": REGISTRY_FORMAT_VERSION,
        "kind": str(kind),
        "family": str(family),
        "rung": int(rung),
        "statics": statics or {},
        "avals": avals,
        "abi": abi_stamp(),
        "code": code_digest(),
    }
    blob = json.dumps(doc, sort_keys=True, default=repr).encode()
    return hashlib.sha256(blob).hexdigest()


def model_family_digest(bundle_dir: str) -> Optional[str]:
    """Content digest identifying a model's computation: the serialized DAG
    (model.json) + fitted parameters (params.npz).  Computed from file
    bytes, so the export side (temp bundle dir) and every later load of the
    renamed bundle — or a byte-identical copy deployed as another tenant —
    agree without a MANIFEST."""
    h = hashlib.sha256()
    found = False
    for name in ("model.json", "params.npz"):
        path = os.path.join(bundle_dir, name)
        try:
            with open(path, "rb") as fh:
                while True:
                    b = fh.read(1 << 20)
                    if not b:
                        break
                    h.update(b)
            found = True
        except OSError:
            h.update(b"-")
    return h.hexdigest()[:24] if found else None


# -- storage layout ----------------------------------------------------------

def _platform_dir(root: str) -> str:
    try:
        import jax
        plat = jax.default_backend()
    except Exception:  # noqa: BLE001 — jax-less host
        plat = "cpu"
    return os.path.join(root, plat)


def entry_dir(key: str, root: Optional[str] = None) -> Optional[str]:
    root = root or registry_root()
    if not root:
        return None
    return os.path.join(_platform_dir(root), key[:2], key)


def _fsync_file(path: str) -> None:
    with contextlib.suppress(OSError):
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)


# -- publish -----------------------------------------------------------------

def publish(key: str, payload: bytes, meta: Optional[Dict[str, Any]] = None,
            root: Optional[str] = None) -> bool:
    """Atomically install ``payload`` under ``key``.  The entry is staged as
    a temp sibling directory (payload + metadata, both fsynced) and renamed
    into place — concurrent publishers of the same key converge on one
    valid entry; the losers' stages are discarded.  Returns True when this
    call (or a racing winner) left a valid entry behind."""
    from .aot import abi_stamp
    from .resilience import record_failure
    final = entry_dir(key, root)
    if final is None:
        return False
    if os.path.isdir(final):
        _count("aot_registry.publish_dedup")
        return True
    parent = os.path.dirname(final)
    tmp = os.path.join(parent,
                       f".tmp-{key[:8]}-{os.getpid()}-{threading.get_ident()}")
    try:
        os.makedirs(tmp, exist_ok=True)
        doc = dict(meta or {})
        doc.update({
            "formatVersion": REGISTRY_FORMAT_VERSION,
            "key": key,
            "abi": abi_stamp(),
            "payloadSha256": hashlib.sha256(payload).hexdigest(),
            "payloadBytes": len(payload),
            "createdAt": time.time(),
        })
        ppath = os.path.join(tmp, ENTRY_PAYLOAD_NAME)
        with open(ppath, "wb") as fh:
            fh.write(payload)
            fh.flush()
            os.fsync(fh.fileno())
        mpath = os.path.join(tmp, ENTRY_META_NAME)
        with open(mpath, "w") as fh:
            json.dump(doc, fh, indent=2, sort_keys=True)
            fh.flush()
            os.fsync(fh.fileno())
        _fsync_file(tmp)
        try:
            os.rename(tmp, final)
        except OSError:
            # a racing publisher renamed first: their entry is equally
            # valid (same content address) — converge, discard ours
            if os.path.isdir(final):
                _count("aot_registry.publish_dedup")
                return True
            raise
        _fsync_file(parent)
        _count("aot_registry.publishes")
        _count("aot_registry.published_bytes", len(payload))
        enforce_budget(root=root)
        return True
    except Exception as e:  # noqa: BLE001 — the registry is optional
        record_failure("aot_registry", "swallowed", e,
                       point="aot_registry.publish", key=key[:16])
        return False
    finally:
        if os.path.isdir(tmp):
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)


# -- lookup / install --------------------------------------------------------

def lookup(key: str, root: Optional[str] = None) -> Optional[bytes]:
    """Digest- and ABI-verified payload for ``key``, or None.  A tampered or
    torn entry is deleted and recorded as ``degraded`` — the caller falls
    back to JIT, and the next publisher repairs the slot."""
    from .aot import abi_mismatch
    from .resilience import record_failure
    d = entry_dir(key, root)
    if d is None or not os.path.isdir(d):
        _count("aot_registry.misses")
        return None
    try:
        with open(os.path.join(d, ENTRY_META_NAME)) as fh:
            meta = json.load(fh)
        if meta.get("formatVersion", 0) > REGISTRY_FORMAT_VERSION:
            _count("aot_registry.misses")
            return None
        reason = abi_mismatch(meta.get("abi"))
        if reason is not None:
            # cross-jaxVersion / platform / machine stamps never install;
            # the entry is not corrupt — another fleet member owns it
            _count("aot_registry.misses")
            _count("aot_registry.abi_skips")
            return None
        ppath = os.path.join(d, ENTRY_PAYLOAD_NAME)
        with open(ppath, "rb") as fh:
            payload = fh.read()
        if hashlib.sha256(payload).hexdigest() != meta.get("payloadSha256"):
            raise ValueError("payload digest mismatch")
        # touch atime for the LRU eviction order (best-effort: noatime
        # mounts fall back to mtime ordering)
        with contextlib.suppress(OSError):
            now = time.time()
            os.utime(ppath, (now, os.stat(ppath).st_mtime))
        _count("aot_registry.hits")
        return payload
    except Exception as e:  # noqa: BLE001
        _count("aot_registry.tampered")
        _count("aot_registry.misses")
        record_failure("aot_registry", "degraded", e,
                       point="aot_registry.lookup", key=key[:16],
                       fallback="JIT compile")
        import shutil
        shutil.rmtree(d, ignore_errors=True)
        return None


def executable_device_ids(compiled) -> List[int]:
    """Ids of the devices ``compiled`` (a ``jax.stages.Compiled``) runs on —
    stored beside every serialized payload so the load side can hand
    ``deserialize_and_load`` the same execution devices."""
    return [int(d.id) for d in compiled.runtime_executable().local_devices()]


def load_executable(payload_rec: Dict[str, Any]) -> Any:
    """``deserialize_and_load`` over the devices the executable was compiled
    for.  Left to its default it loads over EVERY device of the backend, and
    a single-device executable then fails its first call on any host that
    shows more than one ("Expected args ... to have N shards").  A recorded
    id this process does not have raises KeyError — an install failure the
    callers already degrade on."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load
    ids = payload_rec.get("deviceIds")
    devices = None
    if ids is not None:
        by_id = {d.id: d for d in jax.devices()}
        devices = [by_id[i] for i in ids]
    return deserialize_and_load(payload_rec["payload"], payload_rec["inTree"],
                                payload_rec["outTree"],
                                execution_devices=devices)


def shared_load(digest: str, payload_rec: Dict[str, Any]) -> Any:
    """Deserialize ``payload_rec`` (serialize_executable triple) memoized on
    ``digest`` — the cross-tenant seam: every caller installing the same
    payload shares ONE loaded executable and its device memory."""
    with _LOCK:
        fn = _LOADED.get(digest)
        if fn is not None:
            _count("aot_registry.shared_hits")
            return fn
    fn = load_executable(payload_rec)
    with _LOCK:
        # a racing loader may have beaten us — prefer the incumbent so
        # everyone converges on one object
        win = _LOADED.setdefault(digest, fn)
        if win is not fn:
            _count("aot_registry.shared_hits")
        else:
            _count("aot_registry.installs")
    return win


def loaded_count() -> int:
    with _LOCK:
        return len(_LOADED)


def _drop_loaded(digest: str) -> None:
    with _LOCK:
        _LOADED.pop(digest, None)


def _dynamic_kwarg_names(in_tree: Any) -> List[str]:
    """Top-level names of the DYNAMIC keyword arguments a lowered call was
    flattened with.  ``in_tree`` describes ``((args...), {kwargs...})``;
    static_argnames never appear in it, so unflattening the kwargs child
    recovers exactly the traced kwargs (e.g. ``tol``) the executable must
    be called with."""
    import jax
    try:
        children = jax.tree_util.treedef_children(in_tree)
        if len(children) != 2:
            return []
        kwd = children[1]
        proto = jax.tree_util.tree_unflatten(
            kwd, list(range(kwd.num_leaves)))
        if isinstance(proto, dict):
            return sorted(str(k) for k in proto)
    except Exception:  # noqa: BLE001 — fall back to positional-only call
        pass
    return []


# -- fresh serialization (cache-loaded executables) ---------------------------

def cache_loads_reserialize() -> bool:
    """Does an executable jax LOADED from the persistent compile cache
    serialize into something that runs?  On XLA:TPU yes (PR 21 chip run:
    five kinds of program and the save -> serve flow, every compile a cache
    load, zero fallbacks).  On XLA:CPU with jax 0.9 no: the payload
    serializes, deserializes without complaint, and fails at its first CALL
    ("Function ..._fusion not found"), asynchronously, past every except —
    there only an executable BUILT in this process may be serialized."""
    import jax
    return jax.default_backend() != "cpu"


def _reset_jax_compile_cache() -> None:
    """Drop jax's memoized compilation-cache object so the next compile
    re-reads ``jax_compilation_cache_dir``.  jax captures the cache object
    on first use; config updates alone are silently ignored after that."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


# the suspension is process-wide (a jax config value), entered from save()
# on the caller's thread and from publish jobs on the pre-trace thread: the
# outermost entry takes the directory away and the last exit puts it back
_SUSPEND_LOCK = threading.Lock()
_SUSPENDED = {"depth": 0, "dir": None}


@contextlib.contextmanager
def persistent_cache_suspended():
    """Every compile inside the block is a real backend build: the
    persistent cache dir is unset and jax's memoized cache object dropped
    (process-wide — other threads compile uncached meanwhile).  Blocks may
    nest and overlap across threads; the directory comes back, unchanged,
    when the last one exits."""
    import jax
    with _SUSPEND_LOCK:
        if _SUSPENDED["depth"] == 0:
            _SUSPENDED["dir"] = jax.config.jax_compilation_cache_dir
            jax.config.update("jax_compilation_cache_dir", None)
            _reset_jax_compile_cache()
        _SUSPENDED["depth"] += 1
    try:
        yield
    finally:
        with _SUSPEND_LOCK:
            _SUSPENDED["depth"] -= 1
            if _SUSPENDED["depth"] == 0:
                jax.config.update("jax_compilation_cache_dir",
                                  _SUSPENDED["dir"])
                _reset_jax_compile_cache()


def payload_roundtrips(rec: bytes) -> bool:
    """Cheap publishability check: the payload deserializes over the devices
    it was compiled for.  It does NOT prove the executable runs — see
    :func:`cache_loads_reserialize`."""
    try:
        load_executable(pickle.loads(rec))
        return True
    except Exception:  # noqa: BLE001
        return False


def serialize_fresh(fn, args: tuple = (), kwargs: Optional[Dict] = None,
                    label: str = "", maybe_loaded: bool = False
                    ) -> Optional[bytes]:
    """The registry record (serialized executable + pytrees + execution
    devices) of jitted ``fn`` at ``fn.lower(*args, **kwargs)``, or None with
    a ``swallowed`` note — publish is strictly optional.

    Where a cache-loaded executable must not be serialized
    (:func:`cache_loads_reserialize`) the record comes from a build made in
    this process.  jax keeps no provenance on an executable and hands the
    memoized one back to ``lower().compile()``, so the caller says whether
    the dispatch that first compiled the program took a cache hit
    (``maybe_loaded``; ``profiling.thread_cache_hits`` before/after), and
    the same bracket here catches a hit taken by this compile.  Either way
    the program is compiled once more with the persistent cache suspended
    and ``fn``'s own memo cleared (its later dispatches re-trace once): a
    cache-warm process neither ships garbage nor silently skips shipping."""
    from .profiling import thread_cache_hits
    from .resilience import record_failure
    from jax.experimental.serialize_executable import serialize

    def _build() -> bytes:
        compiled = fn.lower(*args, **(kwargs or {})).compile()
        payload, in_tree, out_tree = serialize(compiled)
        buf = io.BytesIO()
        pickle.dump({"payload": payload, "inTree": in_tree,
                     "outTree": out_tree,
                     "deviceIds": executable_device_ids(compiled),
                     "dynKwargs": _dynamic_kwarg_names(in_tree)},
                    buf, protocol=4)
        return buf.getvalue()
    try:
        fresh_only = not cache_loads_reserialize()
        rebuild = fresh_only and maybe_loaded
        if not rebuild:
            hits = thread_cache_hits()
            rec = _build()
            rebuild = fresh_only and thread_cache_hits() != hits
        if rebuild:
            _count("aot_registry.recompiles_for_publish")
            with persistent_cache_suspended():
                fn.clear_cache()
                rec = _build()
        if not payload_roundtrips(rec):
            raise RuntimeError("payload does not deserialize")
        return rec
    except Exception as e:  # noqa: BLE001 — publish is strictly optional
        record_failure("aot_registry", "swallowed", e,
                       point="aot_registry.serialize", detail=label)
        return None


def _queue_publish(key: str, label: str, fn, args: tuple, kwargs: Dict,
                   meta: Optional[Dict[str, Any]] = None,
                   maybe_loaded: bool = False) -> None:
    """Serialize + publish on the background pre-trace thread: the publish
    compile never lands inside a foreground fit/score wall."""
    with _LOCK:
        if key in _PUBLISHED:
            return
        _PUBLISHED.add(key)

    def _job():
        if os.path.isdir(entry_dir(key) or "/nonexistent"):
            _count("aot_registry.publish_dedup")
            return
        rec = serialize_fresh(fn, args, kwargs, label, maybe_loaded)
        if rec is not None:
            publish(key, rec, meta)
    from .aot import pretrace_submit
    pretrace_submit(f"registry-publish:{label}", _job)


# -- the train seam ----------------------------------------------------------

def _single_device_args(args: Any) -> bool:
    """Registry executables are compiled from unsharded host avals; a
    mesh-sharded grid program is a different (GSPMD) computation, so any
    multi-device argument bypasses the registry entirely."""
    import jax
    for leaf in jax.tree_util.tree_leaves(args):
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None:
            try:
                if len(sharding.device_set) > 1:
                    return False
            except Exception:  # noqa: BLE001 — unknown sharding: be safe
                return False
    return True


def _grid_key(label: str, fn_args: tuple,
              sig_statics: Optional[Dict[str, Any]], rung: int) -> str:
    return program_key("grid", label, rung, sig_statics,
                       args_signature(fn_args))


def grid_call(label: str, fn, args: tuple, *,
              static_kwargs: Optional[Dict[str, Any]] = None,
              sig_statics: Optional[Dict[str, Any]] = None,
              rung: Optional[int] = None):
    """Dispatch a batched grid-fit program through the registry.

    Hit: the installed executable runs — zero traces, zero compiles, and
    (via the shared table) one copy of the program per process no matter
    how many candidates/tenants dispatch it.  Miss: the ordinary jit call
    runs (persistent-cache-aware, pre-trace-warmed) and a fresh serialized
    build is published in the background for the rest of the fleet.  Any
    installed-executable failure uninstalls it and retries on the jit path
    — degrade, never break."""
    statics = static_kwargs or {}
    if rung is None:
        first = args[0] if args else None
        rung = int(getattr(first, "shape", (0,))[0] or 0)
    if not (registry_enabled() and _single_device_args(args)):
        _count("aot_registry.bypass")
        return fn(*args, **statics)
    from .resilience import record_failure
    key = _grid_key(label, args, sig_statics or statics, rung)
    with _LOCK:
        loaded = _LOADED.get(key)
    if loaded is None:
        payload = lookup(key)
        if payload is not None:
            try:
                rec = pickle.loads(payload)
                with _LOCK:
                    _DYN_KWARGS[key] = tuple(rec.get("dynKwargs") or ())
                loaded = shared_load(key, rec)
            except Exception as e:  # noqa: BLE001
                record_failure("aot_registry", "degraded", e,
                               point="aot_registry.install", detail=label,
                               fallback="JIT compile")
                _count("aot_registry.install_failures")
                loaded = None
    else:
        _count("aot_registry.hits")
    if loaded is not None:
        try:
            # replay exactly the traced kwargs the executable was lowered
            # with (static_argnames are baked in; traced kwargs like
            # linear_grid_fit's tol must be passed)
            with _LOCK:
                dyn = _DYN_KWARGS.get(key, ())
            return loaded(*args, **{k: statics[k] for k in dyn
                                    if k in statics})
        except Exception as e:  # noqa: BLE001 — shape/ABI drift the stamp
            # could not see: uninstall and fall back to the jit path
            record_failure("aot_registry", "degraded", e,
                           point="aot_registry.call", detail=label,
                           fallback="JIT recompile")
            _count("aot_registry.call_fallbacks")
            _drop_loaded(key)
    from .profiling import thread_cache_hits
    hits = thread_cache_hits()
    out = fn(*args, **statics)
    _queue_publish(key, label, fn, args, statics,
                   {"kind": "grid", "family": label, "rung": int(rung)},
                   maybe_loaded=thread_cache_hits() != hits)
    return out


def grid_compile(label: str, fn, args: tuple, *,
                 static_kwargs: Optional[Dict[str, Any]] = None,
                 sig_statics: Optional[Dict[str, Any]] = None,
                 rung: Optional[int] = None) -> None:
    """Compile-only twin of :func:`grid_call` for the background pre-trace:
    registry hit → deserialize into the shared table NOW (the foreground
    fit then dispatches it with zero compiles); miss → lower+compile as
    before (populating the persistent cache) and publish the fresh build."""
    statics = static_kwargs or {}
    if rung is None:
        first = args[0] if args else None
        rung = int(getattr(first, "shape", (0,))[0] or 0)
    if not (registry_enabled() and _single_device_args(args)):
        fn.lower(*args, **statics).compile()
        return
    key = _grid_key(label, args, sig_statics or statics, rung)
    with _LOCK:
        if key in _LOADED:
            return
    payload = lookup(key)
    if payload is not None:
        try:
            rec = pickle.loads(payload)
            with _LOCK:
                _DYN_KWARGS[key] = tuple(rec.get("dynKwargs") or ())
            shared_load(key, rec)
            return
        except Exception:  # noqa: BLE001 — fall through to the compile
            _count("aot_registry.install_failures")
    rec = serialize_fresh(fn, args, statics, label)
    if rec is not None:
        with _LOCK:
            _PUBLISHED.add(key)
        publish(key, rec, {"kind": "grid", "family": label,
                           "rung": int(rung)})
        with contextlib.suppress(Exception):
            # install our own build too: the foreground fit dispatches the
            # deserialized executable instead of re-tracing through jit
            loaded_rec = pickle.loads(rec)
            with _LOCK:
                _DYN_KWARGS[key] = tuple(loaded_rec.get("dynKwargs") or ())
            shared_load(key, loaded_rec)
    else:
        # unserializable program (or registry write failure): keep the old
        # contract — a plain compile that warms the persistent cache
        fn.lower(*args, **statics).compile()


# -- the scoring seam --------------------------------------------------------

def score_key(family: str, key_tuple: Tuple, arrays: Any) -> str:
    """Content address of one fused scoring program: the model-content
    family digest, the program-table key (stage uids are recorded in
    model.json, so they are stable for every load of the same bundle — and
    for every byte-identical tenant copy), and the input avals.  ``arrays``
    is the call-time pytree or its captured ShapeDtypeStruct specs — both
    hash identically."""
    uids, keep_intermediate, rows = key_tuple
    return program_key("score", family, int(rows),
                       {"uids": list(uids),
                        "keepIntermediate": bool(keep_intermediate)},
                       args_signature(arrays))


def publish_score(family: str, key_tuple: Tuple, program,
                  rec_bytes: bytes, specs: Any = None) -> bool:
    """Publish one export-serialized scoring executable (``aot.py``'s
    ``_serialize_key`` record — a fresh build, the export loop already
    compiles with the persistent cache disabled).  ``specs`` overrides the
    program's first-call avals — the aval-VARIANT seam (ISSUE 19): sparse
    nnz rungs publish one executable per observed input signature under
    the same program-table key."""
    if specs is None:
        specs = program._input_specs.get(key_tuple)
    if specs is None:
        return False
    key = score_key(family, key_tuple, specs)
    return publish(key, rec_bytes,
                   {"kind": "score", "family": family,
                    "rung": int(key_tuple[2])})


def try_install_score(program, key_tuple: Tuple, arrays: Any,
                      sig: Optional[str] = None) -> bool:
    """Consumer side of the scoring seam, called by ``ScoreProgram`` right
    before it would dispatch a freshly-traced program: a registry hit
    installs the published executable over the jit entry, so the call runs
    with zero compiles (pool workers booting on AOT-less bundles, tenants
    activating, lifecycle re-scores).  With ``sig`` (the caller's canonical
    aval signature) the executable installs as a per-(key, sig) VARIANT —
    the registry address already hashes the avals, so each sparse nnz rung
    resolves to its own published build."""
    from .resilience import record_failure
    family = getattr(program, "registry_family", None)
    if not (family and registry_enabled()):
        return False
    try:
        key = score_key(family, key_tuple, arrays)
        payload = lookup(key)
        if payload is None:
            return False
        rec = pickle.loads(payload)
        fn = shared_load(key, rec)
        program.install_executable(key_tuple, fn, rec["canonOut"],
                                   rec["metas"], sig=sig)
        return True
    except Exception as e:  # noqa: BLE001 — stay on the jit path
        record_failure("aot_registry", "degraded", e,
                       point="aot_registry.score_install",
                       fallback="JIT compile")
        _count("aot_registry.install_failures")
        return False


# -- stats / GC --------------------------------------------------------------

def registry_bytes(root: Optional[str] = None) -> int:
    root = root or registry_root()
    if not root or not os.path.isdir(root):
        return 0
    total = 0
    for dirpath, dirnames, filenames in os.walk(root):
        # a compile cache parked under the root is accounted separately
        if os.path.basename(dirpath) == "compile-cache":
            dirnames[:] = []
            continue
        for f in filenames:
            with contextlib.suppress(OSError):
                total += os.stat(os.path.join(dirpath, f)).st_size
    return total


def registry_stats() -> Dict[str, Any]:
    """Counter snapshot + on-disk size — telemetry, /metrics and bench aux
    read this one dict."""
    from .telemetry import REGISTRY
    c = REGISTRY.snapshot()["counters"]

    def g(name: str) -> int:
        return int(c.get(f"aot_registry.{name}", 0))
    return {
        "hits": g("hits"), "misses": g("misses"),
        "publishes": g("publishes"), "evictions": g("evictions"),
        "installs": g("installs"), "shared_hits": g("shared_hits"),
        "bypass": g("bypass"), "tampered": g("tampered"),
        "abi_skips": g("abi_skips"),
        "call_fallbacks": g("call_fallbacks"),
        "recompiles_for_publish": g("recompiles_for_publish"),
        "bytes": registry_bytes(),
        "root": registry_root(),
        "enabled": registry_enabled(),
    }


def _entries(root: str) -> List[Dict[str, Any]]:
    out = []
    for meta_path in glob.glob(os.path.join(
            root, "*", "??", "*", ENTRY_META_NAME)):
        d = os.path.dirname(meta_path)
        size = 0
        atime = 0.0
        for f in (ENTRY_PAYLOAD_NAME, ENTRY_META_NAME):
            with contextlib.suppress(OSError):
                st = os.stat(os.path.join(d, f))
                size += st.st_size
                # LRU rank comes from the PAYLOAD alone: lookup() touches
                # its atime on every hit, whereas entry.json is read by
                # this very scan — counting it would reset the order
                if f == ENTRY_PAYLOAD_NAME:
                    atime = max(atime, st.st_atime, st.st_mtime)
        abi = None
        with contextlib.suppress(Exception):
            with open(meta_path) as fh:
                abi = json.load(fh).get("abi")
        out.append({"dir": d, "bytes": size, "atime": atime, "abi": abi})
    return out


def enforce_budget(root: Optional[str] = None,
                   cap_bytes: Optional[int] = None,
                   keep_min: Optional[int] = None) -> int:
    """Size-capped GC: evict entries (oldest atime first, stale-ABI entries
    before anything else) until the registry fits the byte budget, never
    touching the ``keep_min`` most recently used.  Each eviction leaves an
    ``evicted`` FailureLog note.  Returns the number evicted."""
    from .aot import abi_mismatch
    from .resilience import record_failure
    root = root or registry_root()
    if not root or not os.path.isdir(root):
        return 0
    cap = _cap_bytes() if cap_bytes is None else int(cap_bytes)
    keep = _keep_min() if keep_min is None else int(keep_min)
    entries = _entries(root)
    # stale-ABI first (they can never install here — a fleet of one
    # platform generation keeps only its own), then LRU by atime
    stale = [e for e in entries if abi_mismatch(e["abi"]) is not None]
    fresh = [e for e in entries if abi_mismatch(e["abi"]) is None]
    fresh.sort(key=lambda e: e["atime"])
    total = sum(e["bytes"] for e in entries)
    evicted = 0
    import shutil

    def _evict(e: Dict[str, Any], why: str) -> None:
        nonlocal total, evicted
        shutil.rmtree(e["dir"], ignore_errors=True)
        total -= e["bytes"]
        evicted += 1
        _count("aot_registry.evictions")
        record_failure("aot_registry", "evicted", None,
                       point="aot_registry.gc", entry=os.path.basename(
                           e["dir"])[:16], bytes=e["bytes"], reason=why)
    if total > cap:
        for e in stale:
            if total <= cap:
                break
            _evict(e, "stale ABI")
    evictable = fresh[:-keep] if keep > 0 else fresh
    for e in evictable:
        if total <= cap:
            break
        _evict(e, "LRU under byte budget")
    return evicted


def gc_compile_cache(cache_dir: Optional[str] = None,
                     cap_bytes: Optional[int] = None) -> int:
    """The same LRU-by-atime byte budget for the persistent XLA compile
    cache (it otherwise grows unboundedly — every new shape ladder rung,
    jax upgrade, or workflow variant appends executables forever).  jax's
    cache files are opaque, so eviction is purely LRU; a wrongly-evicted
    entry just recompiles.  Returns the number of files removed."""
    from .resilience import record_failure
    if cache_dir is None:
        import jax
        cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    cap = _cache_cap_bytes() if cap_bytes is None else int(cap_bytes)
    files = []
    for dirpath, _dirnames, filenames in os.walk(cache_dir):
        for f in filenames:
            p = os.path.join(dirpath, f)
            with contextlib.suppress(OSError):
                st = os.stat(p)
                files.append((max(st.st_atime, st.st_mtime), st.st_size, p))
    total = sum(s for _, s, _ in files)
    if total <= cap:
        return 0
    files.sort()
    removed = 0
    for _at, size, p in files:
        if total <= cap:
            break
        with contextlib.suppress(OSError):
            os.unlink(p)
            total -= size
            removed += 1
            _count("aot_registry.cache_evictions")
    if removed:
        record_failure("aot_registry", "evicted", None,
                       point="aot_registry.cache_gc", files=removed,
                       cache=cache_dir, reason="compile cache byte budget")
    return removed
