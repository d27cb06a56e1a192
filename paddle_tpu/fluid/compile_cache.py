"""Persistent compilation cache: AOT-serialized executables on disk.

Every process restart, elastic gang reformation (``distributed.launch``
shrink-to-survivors) and serving cold-start used to re-trace and
re-compile every ``(program, signature, k)`` entry from scratch — the
direct multiplier on elastic-recovery downtime and serving warm-up.
This module gives the executor's in-memory compile cache a second,
on-disk tier built on JAX AOT: the first call of a freshly built step
either ``deserialize_and_load``s a previously serialized executable
(no trace, no XLA compile) or ``lower().compile()``s live, serializes
the result and saves it atomically (tmp+fsync+rename, the PR 4
checkpoint discipline) for the next process.

Keying: the executor's in-memory key leans on ``Program._uid`` — a
process-local monotonic token that means nothing to another process.
The disk key replaces it with a CONTENT hash: program-desc digest
(``Program.serialize_to_string``), feed signature, fetch/state names,
strategy fingerprint (mode + mesh axes/shape + donation setting),
``iters``, the anomaly-policy donation bit, and an environment
fingerprint (jax/jaxlib/XLA versions, platform, device kind, device
count). A stale entry — new jaxlib, different chip, edited program,
re-formed mesh — therefore MISSES cleanly instead of loading garbage.

Robustness contract: a corrupted, truncated or otherwise unloadable
entry is never fatal — it is quarantined (renamed aside, counted in
``compile_cache_quarantined_total``) and the step compiles live. The
same holds for an entry that loads but fails its first call. Concurrent
processes sharing one cache dir are safe: reads see either a complete
entry or none (atomic rename), and the last writer wins.

Disabled (``PADDLE_COMPILE_CACHE_DIR`` unset) the module is inert:
``wrap_jit`` hands back the jit object unchanged, so behavior is
bit-identical to a build without this file.

Beside this private tier sits JAX's own persistent compilation cache
(every ``jit`` in the process, keyed by JAX). ``use_jax_cache`` is what
an entry-point script calls to give it a home; importing the package
never does.
"""

import contextlib
import hashlib
import logging
import os
import pickle
import threading
import time

from . import monitor as _monitor
from . import profiler as _profiler

__all__ = [
    "ENV_DIR", "ENV_MAX_BYTES", "ENTRY_SUFFIX", "PRELOWERED_DIRNAME",
    "cache_dir", "enabled", "active", "override_dir", "program_digest",
    "step_key", "entry_path", "wrap_jit", "prewarm", "disk_hit_count",
    "use_jax_cache",
]

logger = logging.getLogger(__name__)

ENV_DIR = "PADDLE_COMPILE_CACHE_DIR"
ENV_MAX_BYTES = "PADDLE_COMPILE_CACHE_MAX_BYTES"
ENTRY_SUFFIX = ".xc"            # one serialized executable per file
QUARANTINE_SUFFIX = ".quarantined"
PRELOWERED_DIRNAME = "__prelowered__"   # model-adjacent read-only tier
# Bump on any incompatible change to the entry pickle layout — old
# entries then miss via the key hash AND fail the format check.
# 2: entries record the ids of the devices they were compiled for.
FORMAT_VERSION = 2

# -- monitor series -----------------------------------------------------------
_M_DISK_HIT = _monitor.counter(
    "executor_compile_cache_disk_hit_total",
    help="compiled steps served by deserializing an on-disk AOT "
         "executable (no trace, no XLA compile — the restart/cold-start "
         "fast path)")
_M_DISK_MISS = _monitor.counter(
    "executor_compile_cache_disk_miss_total",
    help="disk-tier lookups that found no loadable entry and compiled "
         "live (counted only when a cache dir is configured)")
# tier-labeled views of the executor's hit/miss series: dashboards keyed
# on the unlabeled legacy names keep working, tier={memory,disk} splits
# warm-process hits from restart hits (executor.py owns tier=memory)
_M_HIT_TIER_DISK = _monitor.counter(
    "executor_compile_cache_hit_total",
    help="compile-cache hits by tier",
    labels={"tier": "disk"})
_M_MISS_TIER_DISK = _monitor.counter(
    "executor_compile_cache_miss_total",
    help="compile-cache misses by tier",
    labels={"tier": "disk"})
_M_LOAD_SECONDS = _monitor.histogram(
    "compile_cache_load_seconds",
    help="wall time to read + deserialize_and_load one cache entry "
         "(what a restart pays INSTEAD of trace+compile)")
_M_SAVE_SECONDS = _monitor.histogram(
    "compile_cache_save_seconds",
    help="wall time to serialize + atomically write one cache entry "
         "(paid once per live compile when the cache is enabled)")
_M_QUARANTINED = _monitor.counter(
    "compile_cache_quarantined_total",
    help="corrupted/truncated/unloadable cache entries renamed aside "
         "(the run fell back to a live compile — never fatal)")
_M_EVICTED = _monitor.counter(
    "compile_cache_evicted_total",
    help="cache entries deleted by LRU-by-mtime eviction "
         "(PADDLE_COMPILE_CACHE_MAX_BYTES)")
_M_PREWARMED = _monitor.counter(
    "compile_cache_prewarmed_total",
    help="entries validated and paged in by compile_cache.prewarm "
         "(launcher pre-warm before rendezvous / restore_on_restart)")

_DIR_OVERRIDE = None


# -- configuration ------------------------------------------------------------
def cache_dir():
    """The read-write cache directory, or None when the cache is off.
    ``override_dir`` (the ``save_inference_model(prelower=True)`` path)
    beats the ``PADDLE_COMPILE_CACHE_DIR`` environment variable."""
    if _DIR_OVERRIDE is not None:
        return _DIR_OVERRIDE
    return os.environ.get(ENV_DIR) or None


def enabled():
    return cache_dir() is not None


def active(read_dirs=None):
    """True when any tier could serve or store an entry: the env/override
    write dir, or a read-only dir list (a Predictor's model-adjacent
    ``__prelowered__`` directory works without the env var)."""
    return enabled() or bool(read_dirs)


def max_cache_bytes():
    v = os.environ.get(ENV_MAX_BYTES)
    try:
        return int(v) if v else None
    except ValueError:
        logger.warning("ignoring non-integer %s=%r", ENV_MAX_BYTES, v)
        return None


@contextlib.contextmanager
def override_dir(dirname):
    """Temporarily route the cache at ``dirname`` regardless of the
    environment — ``save_inference_model(prelower=True)`` uses this to
    drop executables next to the model."""
    global _DIR_OVERRIDE
    prev = _DIR_OVERRIDE
    _DIR_OVERRIDE = dirname
    try:
        yield
    finally:
        _DIR_OVERRIDE = prev


# -- keying -------------------------------------------------------------------
def _env_fingerprint():
    """Everything that invalidates a serialized executable without the
    program changing: jax/jaxlib/XLA versions, backend platform, chip
    kind, device count. Part of every key, so a foreign entry misses
    by filename instead of failing to load."""
    import jax

    try:
        import jaxlib

        jaxlib_ver = getattr(jaxlib, "__version__", "?")
    except ImportError:  # pragma: no cover - jaxlib always rides with jax
        jaxlib_ver = "?"
    xla_ver = getattr(getattr(jax, "lib", None), "xla_extension_version",
                      None)
    dev = jax.devices()[0]
    return (FORMAT_VERSION, jax.__version__, jaxlib_ver, xla_ver,
            dev.platform, getattr(dev, "device_kind", "?"),
            jax.device_count())


def program_digest(program):
    """Content hash of the program desc (structure + random_seed), cached
    per mutation counter so repeated key computations don't re-serialize
    the whole desc."""
    cached = getattr(program, "_compile_cache_digest", None)
    if cached is not None and cached[0] == program._mutation:
        return cached[1]
    digest = hashlib.sha256(program.serialize_to_string()).hexdigest()
    program._compile_cache_digest = (program._mutation, digest)
    return digest


def _strategy_fingerprint(strategy):
    if strategy is None:
        return None
    mesh = strategy.mesh
    bs = getattr(strategy, "_build_strategy", None)
    mb_vars = getattr(strategy, "_microbatch_vars", None)
    return (
        getattr(strategy, "_mode", "gspmd"),
        tuple(getattr(strategy, "_mesh_axes", ()) or ()),
        tuple(sorted(mesh.shape.items())) if mesh is not None else None,
        bool(getattr(bs, "enable_inplace", True)),
        getattr(strategy, "_loss_name", None),
        getattr(strategy, "_num_microbatches", None),
        tuple(sorted(mb_vars)) if mb_vars is not None else None,
    )


def step_key(program, feed_sig, fetch_names, state_names, strategy,
             iters, donate):
    """Disk key for one compiled step: the executor's in-memory tuple
    with the process-local ``Program._uid`` replaced by the content
    digest, plus the environment fingerprint. Returns a hex string
    (the entry's filename stem)."""
    parts = (
        _env_fingerprint(),
        program_digest(program),
        tuple(feed_sig),
        tuple(fetch_names),
        tuple(state_names),
        _strategy_fingerprint(strategy),
        int(iters),
        bool(donate),
    )
    return hashlib.sha256(repr(parts).encode("utf-8")).hexdigest()


def entry_path(dirname, key):
    return os.path.join(dirname, key + ENTRY_SUFFIX)


# -- entry I/O ----------------------------------------------------------------
def _quarantine(path):
    """Rename a bad entry aside (never delete: the bytes are evidence)
    so the next lookup misses instead of re-tripping on it."""
    try:
        os.replace(path, path + QUARANTINE_SUFFIX)
    except OSError:
        # a racing process already moved/removed it — equally gone
        pass
    _M_QUARANTINED.inc()


def _load_entry(path):
    """Deserialize one entry into a callable executable, or None
    (quarantining the entry) on ANY failure."""
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            blob = f.read()
        # the single sanctioned deserialization site for cache entries
        # (tools/check_resilience.py lints other pickle.load callers)
        entry = pickle.loads(blob)  # noqa: sanctioned-cache-read
        if not isinstance(entry, dict) or \
                entry.get("format") != FORMAT_VERSION:
            raise ValueError("unrecognized cache entry layout")
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
        )

        import jax

        # execution_devices=None would mean EVERY device of the backend:
        # a one-device step reloaded on a multi-device host would load
        # and then die at its first call expecting one shard per device
        by_id = {d.id: d for d in jax.devices()}
        exe = deserialize_and_load(
            entry["payload"], entry["in_tree"], entry["out_tree"],
            execution_devices=[by_id[i] for i in entry["devices"]])
    except Exception as e:
        logger.warning("compile cache entry %s is unloadable (%s: %s); "
                       "quarantining and compiling live",
                       path, type(e).__name__, e)
        _quarantine(path)
        return None
    _M_LOAD_SECONDS.observe(time.perf_counter() - t0)
    try:
        # LRU-by-mtime: a hit is a use
        os.utime(path, None)
    except OSError:
        pass
    return exe


def _save_entry(dirname, key, compiled, label=""):
    """Serialize + atomically persist one executable; best-effort (a
    full disk or permission error costs the NEXT process a compile,
    never this run)."""
    t0 = time.perf_counter()
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        # the attribute serialize() itself reads the executable from
        devices = [d.id for d in
                   compiled._executable._unloaded_executable.device_list]
        blob = pickle.dumps(
            {"format": FORMAT_VERSION, "label": label, "payload": payload,
             "in_tree": in_tree, "out_tree": out_tree, "devices": devices},
            protocol=pickle.HIGHEST_PROTOCOL)
        os.makedirs(dirname, exist_ok=True)
        from . import io as _io

        _io._atomic_write_bytes(entry_path(dirname, key), blob)
    except Exception as e:
        logger.warning("compile cache save under %s failed (%s: %s); "
                       "continuing uncached", dirname, type(e).__name__, e)
        return False
    _M_SAVE_SECONDS.observe(time.perf_counter() - t0)
    _evict(dirname)
    return True


def _evict(dirname, budget=None):
    """Delete oldest-mtime entries until the dir fits the byte budget
    (``PADDLE_COMPILE_CACHE_MAX_BYTES``; None/0 = unbounded)."""
    budget = max_cache_bytes() if budget is None else budget
    if not budget:
        return 0
    entries = []
    try:
        names = os.listdir(dirname)
    except OSError:
        return 0
    for fn in names:
        if not fn.endswith(ENTRY_SUFFIX):
            continue
        p = os.path.join(dirname, fn)
        try:
            st = os.stat(p)
        except OSError:
            continue
        entries.append((st.st_mtime, st.st_size, p))
    total = sum(sz for _, sz, _ in entries)
    entries.sort()
    evicted = 0
    for _, sz, p in entries:
        if total <= budget:
            break
        try:
            os.remove(p)
        except OSError:
            continue
        total -= sz
        evicted += 1
        _M_EVICTED.inc()
    return evicted


# -- the wrap point -----------------------------------------------------------
def wrap_jit(jfn, key, read_dirs=None, label=""):
    """Give a freshly built ``jax.jit`` callable a disk tier.

    The executor/compiler call this at step-build time (i.e. on an
    in-memory cache MISS). The first real call resolves the executable
    once: try each read dir then the write dir for ``key``; an entry
    that loads and runs skips trace AND compile (disk hit), otherwise
    the step is ``lower().compile()``d live, serialized, and saved (disk
    miss). Subsequent calls go straight to the resolved executable —
    the same object a plain ``jit`` dispatch would use.

    With no cache dir configured (and no ``read_dirs``) or ``key is
    None``, returns ``jfn`` unchanged — the disabled path is
    bit-identical to a build without the cache."""
    write_dir = cache_dir()
    dirs = list(read_dirs or [])
    if write_dir and write_dir not in dirs:
        dirs.append(write_dir)
    if key is None or not dirs:
        return jfn

    resolved = []
    lock = threading.Lock()

    def _first_call(args):
        """Resolve the executable AND run it once. A disk entry counts
        as a hit only after its first call returns: one that loads but
        cannot execute here is quarantined like any other bad entry.
        (A call that fails only after consuming donated buffers leaves
        the live compile nothing to run on; that error propagates.)"""
        for d in dirs:
            path = entry_path(d, key)
            if not os.path.exists(path):
                continue
            with _profiler.RecordEvent(_profiler.SPAN_CACHE_LOAD):
                exe = _load_entry(path)
                if exe is None:
                    continue
                try:
                    out = exe(*args)
                except Exception:
                    logger.warning(
                        "compile cache entry %s loaded but failed its "
                        "first call; quarantining and compiling live",
                        path, exc_info=True)
                    _quarantine(path)
                    continue
            _M_DISK_HIT.inc()
            _M_HIT_TIER_DISK.inc()
            return exe, out
        _M_DISK_MISS.inc()
        _M_MISS_TIER_DISK.inc()
        # AOT lowering is the same trace a plain call does: a failure
        # here would have failed the undecorated jit the same way
        with _profiler.RecordEvent(_profiler.SPAN_CACHE_COMPILE):
            compiled = jfn.lower(*args).compile()
        if write_dir:
            with _profiler.RecordEvent(_profiler.SPAN_CACHE_SAVE):
                _save_entry(write_dir, key, compiled, label=label)
        return compiled, compiled(*args)

    def call(*args):
        if not resolved:
            with lock:
                if not resolved:
                    exe, out = _first_call(args)
                    resolved.append(exe)
                    return out
        return resolved[0](*args)

    return call


# -- pre-warm (launcher / restart path) ---------------------------------------
def prewarm(dirname=None):
    """Validate + page in every entry under ``dirname`` (default: the
    configured cache dir). Runs in the LAUNCHER before rendezvous
    completes, and in ``restore_on_restart`` — so a reformed gang's
    workers find entries hot in the page cache and corrupt ones already
    quarantined, instead of discovering both inside the downtime
    window. Does NOT load executables onto devices (the launcher must
    not claim the chips). Returns the number of valid entries."""
    dirname = dirname or cache_dir()
    if not dirname or not os.path.isdir(dirname):
        return 0
    ok = 0
    for fn in sorted(os.listdir(dirname)):
        if not fn.endswith(ENTRY_SUFFIX):
            continue
        path = os.path.join(dirname, fn)
        try:
            with open(path, "rb") as f:
                blob = f.read()
            # structural validation only; devices stay untouched
            entry = pickle.loads(blob)  # noqa: sanctioned-cache-read
            if not isinstance(entry, dict) or \
                    entry.get("format") != FORMAT_VERSION or \
                    "payload" not in entry:
                raise ValueError("unrecognized cache entry layout")
        except Exception as e:
            logger.warning("prewarm: quarantining bad cache entry %s "
                           "(%s: %s)", path, type(e).__name__, e)
            _quarantine(path)
            continue
        ok += 1
        _M_PREWARMED.inc()
    return ok


def disk_hit_count():
    """Current value of the disk-hit counter (serving warm-up snapshots
    it around the ladder to report how many compiles a restart skipped)."""
    return _M_DISK_HIT.value


# -- JAX's own persistent compilation cache -----------------------------------
def use_jax_cache():
    """Give JAX's persistent compilation cache a home, for entry-point
    scripts (``chip_smoke.py``, ``benchmark/run.py``) — never called on import,
    so a test run writes no executables into the checkout.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is set here. Otherwise the cache goes to ``.jax_cache``
    at the root of the checkout: a path fixed by this file's location,
    because the directory is part of what a later process must find
    again. Returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
