"""DataLoader / PyReader: the host->device input pipeline.

Parity: reference ``python/paddle/fluid/reader.py`` (``DataLoader:73``
``from_generator``, ``GeneratorLoader:298``, ``PyReader:583``) backed by
C++ ``LoDTensorBlockingQueue`` + ``buffered_reader`` (pre-H2D transfer on a
CUDA stream). TPU-native: a background ``DeviceStager`` thread assembles
numpy batches and stages them on device with ``jax.device_put`` ahead of
consumption — the double-buffer H2D overlap matters even more here because
the chip can sit behind a high-latency host link; the
executor accepts the staged ``jax.Array`` feeds untouched.

Staging is SHARDING-AWARE: pass ``sharding=`` (a ``CompiledProgram``, a
{name: Sharding} dict, or a ``fn(name, value) -> Sharding|None``) and each
feed lands pre-laid-out with the program's GSPMD feed ``NamedSharding``
(``CompiledProgram.feed_sharding``) instead of funneling through device 0 —
a data-parallel program then consumes the prefetched batch with zero
resharding copies.
"""

import os as _os
import queue as _queue
import threading
import time as _time

import numpy as np

from . import monitor as _monitor
from . import resilience as _resilience
from .framework import Variable

__all__ = ["DataLoader", "PyReader", "GeneratorLoader", "DeviceStager",
           "stage_feed", "WorkerInfo", "get_worker_info"]

# -- monitor series (process-wide; see fluid/monitor.py) ----------------------
_M_BATCHES = _monitor.counter(
    "reader_batches_total",
    help="batches produced by DataLoader/GeneratorLoader")
_M_STALLS = _monitor.counter(
    "reader_queue_full_total",
    help="producer stalls: the prefetch queue was full when a batch "
         "was ready (consumer is the bottleneck)")
_M_FEED_SECONDS = _monitor.histogram(
    "reader_feed_seconds",
    help="batch assembly + device staging time (_to_feed)")
_M_PREFETCH_DEPTH = _monitor.gauge(
    "reader_prefetch_depth",
    help="staged batches queued ahead of the consumer (DeviceStager "
         "queue occupancy; capacity-bounded)")
_M_PREFETCH_STALL = _monitor.histogram(
    "reader_prefetch_stall_seconds",
    help="consumer wait on the DeviceStager queue (0 when the next "
         "staged batch was already waiting — the prefetch kept up)")

# transient staging failures (a device_put hiccup on a flaky host link,
# an injected reader.stage fault) are retried with backoff inside the
# producer thread instead of killing the whole input pipeline; attempts
# are tunable via PADDLE_STAGE_RETRIES (>=1), and every retry/exhaustion
# is counted under site="reader.stage" in monitor
_STAGE_RETRY = _resilience.Retry(
    max_attempts=max(1, int(_os.environ.get("PADDLE_STAGE_RETRIES", "3"))),
    base_delay=0.05, max_delay=1.0,
    retryable=_resilience.TransientError, name="reader.stage")


def _as_sharding_fn(sharding):
    """Normalize the ``sharding=`` surface to ``fn(name, value) ->
    Sharding|None``: None passes through, a ``CompiledProgram`` resolves
    via its ``feed_sharding``, a dict looks names up, a callable is used
    as-is."""
    if sharding is None:
        return None
    if hasattr(sharding, "feed_sharding"):  # CompiledProgram strategy
        return lambda name, value: sharding.feed_sharding(value, name=name)
    if isinstance(sharding, dict):
        return lambda name, value: sharding.get(name)
    if callable(sharding):
        return sharding
    raise TypeError(
        "sharding must be None, a CompiledProgram, a {name: Sharding} "
        "dict, or fn(name, value) -> Sharding; got %r" % (sharding,))


def stage_feed(feed, sharding_fn=None):
    """Sharding-aware H2D staging of one feed dict: every ndarray /
    jax.Array value is ``jax.device_put`` with the sharding
    ``sharding_fn(name, value)`` resolves (plain single-device put when
    the fn is absent or returns None); non-array values (LoDTensor etc.)
    pass through raw — the executor decomposes those itself."""
    import jax

    from . import faults as _faults

    _faults.check("reader.stage")
    out = {}
    for name, value in feed.items():
        if isinstance(value, (np.ndarray, jax.Array)):
            s = sharding_fn(name, value) if sharding_fn is not None else None
            value = jax.device_put(value, s) if s is not None \
                else jax.device_put(value)
        out[name] = value
    return out


class DeviceStager:
    """Bounded ahead-of-time staging pipeline: a producer thread pulls
    items from ``source``, runs ``transform`` (batch assembly and/or the
    sharding-aware ``jax.device_put``), and hands results over a bounded
    queue — H2D transfer for batch i+1 overlaps the device's step i, and
    ``reader_prefetch_depth`` reports how far ahead it is running.

    The thread is deliberately NON-daemon: a stager that outlives its
    pipeline is a bug (tests/conftest.py fails any test that leaks one).
    Iterate to exhaustion or call ``close()`` — close() is idempotent,
    unblocks a producer stalled on a full queue, and joins the thread.
    Producer exceptions re-raise in the consumer."""

    _END = object()

    def __init__(self, source, transform=None, capacity=2, name="stager"):
        self._q = _queue.Queue(maxsize=max(1, int(capacity)))
        self._stop = threading.Event()
        self._done = False
        self._transform = transform
        self._source = iter(source)
        self._thread = threading.Thread(
            target=self._produce, name="paddle-device-stager[%s]" % name,
            daemon=False)
        self._thread.start()

    # -- producer side --------------------------------------------------
    def _put(self, item):
        # consumer-bound: count the stall once per batch — checked up
        # front because the blocking put below can absorb a short stall
        # inside its timeout without ever raising Full
        if self._q.full():
            _M_STALLS.inc()
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                _M_PREFETCH_DEPTH.set(self._q.qsize())
                return True
            except _queue.Full:
                pass
        return False

    def _produce(self):
        try:
            for item in self._source:
                if self._stop.is_set():
                    return
                if self._transform is not None:
                    # transient staging failures retry with backoff here,
                    # on the producer thread, so a device_put hiccup
                    # doesn't tear down the whole input pipeline
                    item = _STAGE_RETRY.call(self._transform, item)
                if not self._put(item):
                    return
        except BaseException as e:  # background thread: stored and re-raised on the consumer side
            self._put(("__stager_error__", e))
        finally:
            self._put(self._END)

    # -- consumer side --------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        t0 = _time.perf_counter()
        item = self._q.get()
        _M_PREFETCH_STALL.observe(_time.perf_counter() - t0)
        _M_PREFETCH_DEPTH.set(self._q.qsize())
        if item is self._END:
            self.close()
            raise StopIteration
        if isinstance(item, tuple) and len(item) == 2 and \
                item[0] == "__stager_error__":
            self.close()
            raise item[1]
        return item

    def close(self):
        """Stop the producer and join its thread. Items still queued are
        dropped (an abandoned prefetch is by definition ahead of what
        the consumer wanted)."""
        if self._done and not self._thread.is_alive():
            return
        self._done = True
        self._stop.set()
        # drain so a producer blocked on a full queue can observe _stop
        while True:
            try:
                self._q.get_nowait()
            except _queue.Empty:
                break
        self._thread.join()
        _M_PREFETCH_DEPTH.set(0)


class WorkerInfo:
    """Identity of the current DataLoader worker process. A generator
    that wants to avoid duplicate parsing shards its own input by
    ``get_worker_info()`` and then calls ``mark_sharded()`` so the loader
    keeps every batch it yields instead of round-robin filtering."""

    def __init__(self, rank, num_workers):
        self.id = rank
        self.num_workers = num_workers
        self.consumed_shard = False

    def mark_sharded(self):
        self.consumed_shard = True


_worker_info = None


def get_worker_info():
    """None in the main process; a WorkerInfo inside an mp worker."""
    return _worker_info


class GeneratorLoader:
    """Iterable loader: wraps a sample/batch generator into prefetched,
    device-staged feed dicts. ``use_double_buffer=False`` turns BOTH the
    prefetch thread and the ahead-of-time device staging off — every
    batch assembles synchronously in the consumer and reaches the
    executor as host arrays (staged at dispatch)."""

    def __init__(self, feed_list, capacity=4, stage_on_device=True,
                 use_multiprocess=False, num_workers=2,
                 use_double_buffer=True, sharding=None):
        self._feed_names = [v.name if isinstance(v, Variable) else str(v)
                            for v in feed_list]
        self._feed_vars = feed_list
        self._capacity = capacity
        self._stage = stage_on_device
        self._double_buffer = bool(use_double_buffer)
        self._sharding_fn = _as_sharding_fn(sharding)
        self._gen = None
        self._kind = None
        self._use_multiprocess = use_multiprocess
        self._num_workers = max(1, int(num_workers))

    # -- generator registration (reference reader.py:419-520) -----------
    def set_sample_generator(self, generator, batch_size, drop_last=True):
        def batcher():
            buf = []
            for sample in generator():
                buf.append(sample if isinstance(sample, (list, tuple))
                           else (sample,))
                if len(buf) == batch_size:
                    yield [np.stack([np.asarray(s[i]) for s in buf])
                           for i in range(len(buf[0]))]
                    buf = []
            if buf and not drop_last:
                yield [np.stack([np.asarray(s[i]) for s in buf])
                       for i in range(len(buf[0]))]

        self._gen = batcher
        return self

    def set_sample_list_generator(self, generator):
        def batcher():
            for samples in generator():
                yield [np.stack([np.asarray(s[i]) for s in samples])
                       for i in range(len(samples[0]))]

        self._gen = batcher
        return self

    def set_batch_generator(self, generator):
        self._gen = generator
        return self

    # -- iteration -------------------------------------------------------
    def _to_feed(self, batch):
        t0 = _time.perf_counter()
        items = ([batch[n] for n in self._feed_names]
                 if isinstance(batch, dict) else list(batch))
        arrays = []
        for name, a in zip(self._feed_names, items):
            # LoDTensors pass through whole; the executor decomposes them
            # into data + @LOD lengths itself
            if hasattr(a, "recursive_sequence_lengths"):
                arrays.append(a)
                continue
            a = np.asarray(a)
            if self._stage and self._double_buffer:
                import jax

                # async H2D with the program's feed sharding: stages
                # ahead (and pre-shards) while the step runs
                s = self._sharding_fn(name, a) \
                    if self._sharding_fn is not None else None
                a = jax.device_put(a, s) if s is not None \
                    else jax.device_put(a)
            arrays.append(a)
        _M_FEED_SECONDS.observe(_time.perf_counter() - t0)
        _M_BATCHES.inc()
        return dict(zip(self._feed_names, arrays))

    def _iter_threaded(self):
        stager = DeviceStager(self._gen(), transform=self._to_feed,
                              capacity=self._capacity, name="loader")
        try:
            for item in stager:
                yield item
        finally:
            # abandoning the loop (break / GC of the generator) must not
            # leak the non-daemon producer thread
            stager.close()

    def _iter_sync(self):
        """use_double_buffer=False: no thread, no queue, no device
        staging — each batch assembles on demand in the consumer."""
        for batch in self._gen():
            yield self._to_feed(batch)

    def _iter_multiprocess(self):
        """Worker processes run the generator and ship numpy batches over
        an mp queue; device staging stays in the parent (reference
        reader.py:73 _DataLoaderIterMultiProcess + shared-memory channel;
        fork + pickle is the TPU-host equivalent — parsing/augmentation
        escapes the GIL, the H2D stays on the process that owns the
        device client).

        Sharding: each worker runs the full generator and keeps batches
        round-robin by index — correct for any generator, but parse work
        multiplies by num_workers unless the generator shards itself via
        ``get_worker_info()`` (then every yielded batch is kept)."""
        import multiprocessing as mp
        import traceback

        ctx = mp.get_context("fork")
        q = ctx.Queue(maxsize=max(2, self._capacity))
        n = self._num_workers

        def pack(a):
            # LoDTensors must survive the queue with their lengths
            if hasattr(a, "recursive_sequence_lengths"):
                return ("__lod__", np.asarray(a),
                        a.recursive_sequence_lengths())
            return np.asarray(a)

        def worker(rank, gen, nworkers):
            global _worker_info
            _worker_info = WorkerInfo(rank, nworkers)
            try:
                for i, batch in enumerate(gen()):
                    if _worker_info.consumed_shard is False and \
                            i % nworkers != rank:
                        continue  # round-robin split of the batch stream
                    if isinstance(batch, dict):
                        items = [batch[k] for k in self._feed_names]
                    else:
                        items = list(batch)
                    q.put([pack(a) for a in items])
                q.put(None)
            except BaseException:  # forked worker: traceback shipped to the parent, re-raised there
                q.put(("__worker_error__", rank,
                       traceback.format_exc()))

        procs = [ctx.Process(target=worker, args=(r, self._gen, n),
                             daemon=True) for r in range(n)]
        for p in procs:
            p.start()

        def unpack(a):
            if isinstance(a, tuple) and len(a) == 3 and a[0] == "__lod__":
                from .lod import LoDTensor

                return LoDTensor(a[1], a[2])
            return a

        done = 0
        try:
            while done < n:
                item = q.get()
                if item is None:
                    done += 1
                    continue
                if isinstance(item, tuple) and item[0] == "__worker_error__":
                    raise RuntimeError(
                        "DataLoader worker %d died:\n%s"
                        % (item[1], item[2]))
                yield self._to_feed([unpack(a) for a in item])
        finally:
            for p in procs:
                p.terminate()
                p.join()

    def __iter__(self):
        if self._gen is None:
            raise RuntimeError("no generator set (set_batch_generator / "
                               "set_sample_generator / set_sample_list_generator)")
        if self._use_multiprocess:
            return self._iter_multiprocess()
        if not self._double_buffer:
            return self._iter_sync()
        return self._iter_threaded()


class DataLoader:
    """Reference ``reader.py:73``. ``from_generator`` is the supported
    path (``from_dataset`` arrives with the Dataset/trainer stack)."""

    @staticmethod
    def from_generator(feed_list=None, capacity=4, use_double_buffer=True,
                       iterable=True, return_list=False,
                       stage_on_device=True, use_multiprocess=False,
                       num_workers=2, sharding=None):
        """``use_double_buffer=True`` (default): a background
        ``DeviceStager`` thread prefetches up to ``capacity`` batches,
        each already assembled and — with ``stage_on_device=True`` —
        ``jax.device_put`` ahead of time (pass ``sharding=`` a
        ``CompiledProgram`` / dict / fn to pre-shard for GSPMD).
        ``use_double_buffer=False``: fully synchronous — no prefetch
        thread AND no ahead-of-time device staging (feeds reach the
        executor as host arrays and stage at dispatch); use it when
        batches are produced by something that must not run on a
        side thread, or to take H2D off the measurement."""
        if not feed_list:
            raise ValueError("feed_list is required")
        return GeneratorLoader(feed_list, capacity=capacity,
                               stage_on_device=stage_on_device,
                               use_multiprocess=use_multiprocess,
                               num_workers=num_workers,
                               use_double_buffer=use_double_buffer,
                               sharding=sharding)

    @staticmethod
    def from_dataset(dataset, places=None, drop_last=True):
        """Iterate a Dataset's batches as prefetched, device-staged feed
        dicts (reference ``reader.py:145``)."""
        loader = GeneratorLoader(dataset._use_vars)
        loader.set_batch_generator(dataset.batch_reader(drop_last))
        return loader


class PyReader:
    """Reference ``reader.py:583``: the older decorate_* API over the same
    machinery; ``start()``/``reset()`` are no-ops in iterable mode."""

    def __init__(self, feed_list=None, capacity=4, use_double_buffer=True,
                 iterable=True, return_list=False, sharding=None):
        self._loader = GeneratorLoader(feed_list, capacity,
                                       use_double_buffer=use_double_buffer,
                                       sharding=sharding)
        self._iterable = iterable

    def decorate_sample_generator(self, sample_generator, batch_size,
                                  drop_last=True, places=None):
        self._loader.set_sample_generator(sample_generator, batch_size,
                                          drop_last)

    def decorate_sample_list_generator(self, reader, places=None):
        self._loader.set_sample_list_generator(reader)

    def decorate_batch_generator(self, reader, places=None):
        self._loader.set_batch_generator(reader)

    def start(self):
        pass

    def reset(self):
        pass

    def __iter__(self):
        return iter(self._loader)
