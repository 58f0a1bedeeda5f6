"""DataLoader.

Reference parity: python/mxnet/gluon/data/dataloader.py — DataLoader with
batchify (default_batchify_fn), samplers, and multi-worker loading.

TPU-first notes:

- **Single-copy collation**: ``default_batchify_fn`` collates samples into
  one preallocated contiguous host buffer and issues exactly ONE async
  ``jax.device_put`` per batch array — no per-sample host→device
  transfers, no device-side ``jnp.stack`` (the pre-round-3 path issued
  one transfer per *sample*; see docs/perf.md "Input pipeline").
- **Workers**: host-side decode/augment uses a thread pool by default
  (numpy/PIL release the GIL for the heavy parts, and threads avoid
  re-importing jax per worker); ``thread_pool=False`` with num_workers>0
  spawns processes that transport batches through shared-memory ring
  slots (``_shm_worker.py``) instead of pickling, with out-of-order
  completion and in-order delivery — a slow worker delays only its own
  batch.  ``MXTPU_SHM_SLOT_MB`` sizes the ring slots; oversized batches
  fall back to pickle transport transparently.
- Device placement overlap lives one layer up: wrap any loader in
  ``mxnet_tpu.gluon.data.DevicePrefetcher`` (prefetcher.py).
"""

from __future__ import annotations

import collections
import multiprocessing
import os
import queue as _queue
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FutTimeout

import numpy as _np

from ... import resilience as _resilience
from ... import telemetry as _telemetry
from ...ndarray.ndarray import NDArray, _from_jax
from . import sampler as _sampler
from . import _shm_worker
from .state import DataPipelineState


class DataLoaderWorkerError(RuntimeError):
    """A dataset ``__getitem__``/batchify raised inside a loader worker.

    Carries the failing batch's sample indices and (for process workers)
    the worker-side traceback, instead of the opaque pickling/timeout
    error the raw transport would produce."""


def _on_host(nd):
    """True when an NDArray's buffer lives on the host platform (so a
    per-sample ``asnumpy`` is a cheap view/copy, not a device readback)."""
    try:
        return next(iter(nd._data.devices())).platform == "cpu"
    except Exception:
        return True


def _wrap_device(collated):
    """One async ``jax.device_put`` per collated batch array."""
    if isinstance(collated, list):
        return [_wrap_device(c) for c in collated]
    import jax

    return _from_jax(jax.device_put(collated))


def default_batchify_fn(data):
    """Stack samples into a batch (reference: default_batchify_fn).

    Collates on the host into one contiguous buffer per output array and
    performs a single async device transfer per array."""
    if isinstance(data[0], NDArray) and not _on_host(data[0]):
        # device-resident samples: stacking on-device beats a readback
        import jax.numpy as jnp

        return _from_jax(jnp.stack([d._data for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(list(i)) for i in data]
    return _wrap_device(_shm_worker.collate_column(data))


def default_mp_batchify_fn(data):
    """Batchify in a worker: keep numpy (single-copy collation into a
    contiguous buffer); the parent wraps with one device_put per array."""
    return _shm_worker.collate_samples(data)


def _as_in_context(data, ctx):
    if isinstance(data, NDArray):
        return data.as_in_context(ctx)
    if isinstance(data, (list, tuple)):
        return [_as_in_context(d, ctx) for d in data]
    return data


class _Worker:
    """Picklable per-batch fetch closure for pool workers."""

    def __init__(self, dataset, batchify_fn):
        self._dataset = dataset
        self._batchify_fn = batchify_fn

    def __call__(self, samples, batch_idx=None):
        if batch_idx is not None:
            # worker_hang:K / data_skew:K fault sites (thread transport;
            # _shm_worker mirrors this for spawn workers)
            _resilience.maybe_data_fault(batch_idx)
        return self._batchify_fn([self._dataset[i] for i in samples])


class DataLoader:
    """Loads mini-batches from a Dataset (reference: gluon.data.DataLoader).

    Parameters follow the reference: dataset, batch_size, shuffle, sampler,
    last_batch ('keep'|'discard'|'rollover'), batch_sampler, batchify_fn,
    num_workers, pin_memory (ignored: XLA host buffers are already pinned),
    prefetch (None -> 2*num_workers; 0 -> at most one batch in flight),
    thread_pool.

    TPU-first additions (exactly-once resumable pipeline, see
    ``gluon/data/state.py``):

    - ``seed``: opting in makes the loader **resumable** — the sample
      order becomes a pure function of ``(seed, epoch)``, the loader
      exposes ``state_dict()/load_state_dict()`` (epoch, global sample
      cursor, quarantined batches) for the checkpoint path, and replay
      after a `DivergenceMonitor` rollback skips quarantined batches
      with one ``batch_quarantined`` telemetry event each.
    - ``rank``/``world_size``: this loader's slice of the global order
      (``order[cursor:][rank::world]``).  A restored state keeps the
      LOCAL rank/world, so an elastic N→M reshape re-shards the
      remaining epoch deterministically with zero re-read and zero
      skipped samples.
    - ``MXTPU_DATA_TIMEOUT`` (seconds, default = ``timeout``): receive
      watchdog for worker batches — a hung worker raises
      `DataLoaderWorkerError` naming the batch instead of blocking the
      training step past the gang's heartbeat window.
    """

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, pin_device_id=0,
                 prefetch=None, thread_pool=True, timeout=120,
                 seed=None, rank=0, world_size=1):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._thread_pool = thread_pool
        self._timeout = timeout
        self._state = None

        if seed is not None:
            if batch_sampler is not None or sampler is not None:
                raise ValueError(
                    "seed= (resumable loading) builds its own sampler; "
                    "it cannot be combined with sampler= or "
                    "batch_sampler=")
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified with seed=")
            self._state = DataPipelineState(
                len(dataset), seed=seed, shuffle=shuffle,
                rank=rank, world=world_size)
            sampler = _sampler.ResumableSampler(self._state)
            shuffle = False   # the ResumableSampler owns the order

        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size must be specified unless batch_sampler is "
                    "specified")
            if sampler is None:
                if shuffle:
                    sampler = _sampler.RandomSampler(len(dataset))
                else:
                    sampler = _sampler.SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError(
                    "shuffle must not be specified if sampler is specified")
            batch_sampler = _sampler.BatchSampler(
                sampler, batch_size, last_batch if last_batch else "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not be "
                "specified if batch_sampler is specified.")

        self._batch_sampler = batch_sampler
        self._num_workers = num_workers if num_workers >= 0 else 0
        self._prefetch = max(0, int(prefetch) if prefetch is not None
                             else 2 * self._num_workers)
        self._custom_batchify = batchify_fn is not None
        if batchify_fn is None:
            if num_workers > 0 and not thread_pool:
                self._batchify_fn = default_mp_batchify_fn
            else:
                self._batchify_fn = default_batchify_fn
        else:
            self._batchify_fn = batchify_fn

    def __iter__(self):
        if self._state is None:
            return self._raw_iter(iter(self._batch_sampler))
        return _ResumableIter(self)

    def _raw_iter(self, batches):
        """The transport-level iterator over an index-batch stream."""
        if self._num_workers == 0:
            def same_process_iter():
                for batch in batches:
                    ret = self._batchify_fn(
                        [self._dataset[idx] for idx in batch])
                    yield ret
            return same_process_iter()
        return _MultiWorkerIter(self, batches)

    def __len__(self):
        return len(self._batch_sampler)

    # -- resumable pipeline state (gluon/data/state.py) ------------------------

    def _require_state(self, what):
        if self._state is None:
            raise RuntimeError(
                f"DataLoader.{what}: construct the loader with seed= to "
                f"make it resumable")
        return self._state

    def state_dict(self):
        """JSON-serializable pipeline position (delivery-exact: never
        counts prefetched-but-undelivered batches)."""
        return self._require_state("state_dict").state_dict()

    def load_state_dict(self, sd):
        """Adopt a checkpointed position; the next ``__iter__`` resumes
        at the exact sample offset (zero re-read, zero skipped).  The
        loader's own rank/world are kept — loading an N-rank state into
        an M-rank loader IS the elastic re-shard."""
        st = self._require_state("load_state_dict")
        st.load_state_dict(sd)
        _telemetry.event("data_resume", epoch=st.epoch, cursor=st.cursor,
                         samples_seen=st.samples_seen,
                         reread_samples=0, skipped_samples=0,
                         world=st.world, loader_rank=st.rank)
        return self

    def quarantine(self, batch_ids):
        """Mark ``(epoch, batch_idx)`` batch ids to be skipped (loudly)
        on replay — the `DivergenceMonitor` rollback hookup."""
        self._require_state("quarantine").quarantine(batch_ids)

    def last_batch_id(self):
        """``(epoch, batch_idx)`` of the newest delivered batch (what
        the Trainer reports to `DivergenceMonitor.observe`)."""
        return self._require_state("last_batch_id").last_delivered

    @property
    def samples_seen(self):
        return self._require_state("samples_seen").samples_seen


def _slot_bytes():
    return int(float(os.environ.get("MXTPU_SHM_SLOT_MB", 32)) * (1 << 20))


class _MultiWorkerIter:
    """Prefetching iterator over pool workers.

    Thread pool: futures are delivered in submit order; the executor runs
    them concurrently.  Process pool: workers pull from a shared task
    queue (out-of-order completion), results are reordered in the parent
    so delivery matches the sampler order — identical batches, identical
    order, regardless of transport.

    The iterator owns OS resources; it cleans up on exhaustion, on
    ``close()``, on ``__del__`` (abandoned mid-epoch), and supports use
    as a context manager.
    """

    def __init__(self, loader, batches=None):
        self._loader = loader
        self._batches = iter(loader._batch_sampler) if batches is None \
            else iter(batches)
        # receive watchdog: how long a delivery may wait on one worker
        # result before declaring it hung (default: the transport
        # timeout) — keeps a wedged worker from stalling step_tick past
        # the gang's heartbeat window
        self._data_timeout = float(
            os.environ.get("MXTPU_DATA_TIMEOUT", loader._timeout))
        self._depth = max(1, loader._prefetch)
        self._sent_idx = 0
        self._rcvd_idx = 0
        self._data_buffer = {}  # batch_idx -> result record
        self._closed = False
        self._pool = None
        self._procs = []
        if loader._thread_pool:
            self._worker = _Worker(loader._dataset, loader._batchify_fn)
            self._pool = ThreadPoolExecutor(max_workers=loader._num_workers)
        else:
            self._start_processes(loader)
        for _ in range(self._depth):
            self._push_next()

    # -- process transport -----------------------------------------------------

    def _start_processes(self, loader):
        ctx = multiprocessing.get_context("spawn")
        nslots = max(self._depth, loader._num_workers)
        self._slots = [ctx.RawArray("b", _slot_bytes())
                       for _ in range(nslots)]
        self._free_slots = list(range(nslots))
        self._task_q = ctx.Queue()
        self._result_q = ctx.Queue()
        # None selects the jax-free built-in collation in the worker; a
        # pickled reference to the default fn would drag the whole
        # package (and jax) into every spawned child
        fn = loader._batchify_fn if loader._custom_batchify else None
        # a spawned child takes os.environ as it stands at start(): hold
        # the workers to the host CPU, so that a dataset which builds an
        # NDArray in a worker cannot claim the chip from under the
        # trainer (one process per chip).  This process's own JAX read
        # the variable at import and is not affected.
        saved = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for _ in range(loader._num_workers):
                p = ctx.Process(
                    target=_shm_worker.worker_loop,
                    args=(loader._dataset, fn, self._slots, self._task_q,
                          self._result_q),
                    daemon=True)
                p.start()
                self._procs.append(p)
        finally:
            if saved is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = saved

    def _push_next(self):
        if self._closed:
            return
        if self._pool is None and not self._free_slots:
            return  # every ring slot is in flight
        batch = next(self._batches, None)
        if batch is None:
            return
        if self._pool is not None:
            fut = self._pool.submit(self._worker, batch, self._sent_idx)
            self._data_buffer[self._sent_idx] = ("future", fut, batch)
        else:
            slot = self._free_slots.pop()
            self._task_q.put((self._sent_idx, slot, list(batch)))
        self._sent_idx += 1

    def _recv_until(self, idx):
        """Drain the result queue until batch `idx` has arrived.

        Slots are copied out and recycled at *receive* time, not delivery
        time, so an out-of-order fast batch never pins a slot while a
        slow one is pending."""
        while idx not in self._data_buffer:
            try:
                msg = self._result_q.get(timeout=self._data_timeout)
            except _queue.Empty:
                alive = [p.pid for p in self._procs if p.is_alive()]
                self.close(wait=False)
                self._note_timeout(idx)
                raise DataLoaderWorkerError(
                    f"DataLoader worker result for batch {idx} not "
                    f"received within MXTPU_DATA_TIMEOUT="
                    f"{self._data_timeout}s (hung worker? live worker "
                    f"pids: {alive})")
            tag, bidx, slot, payload, is_list = msg
            if tag == "shm":
                out = _shm_worker.read_slot(self._slots[slot], payload,
                                            is_list)
                self._data_buffer[bidx] = ("data", out, None)
            elif tag == "pickle":
                self._data_buffer[bidx] = ("data", payload, None)
            else:  # "error"
                self._data_buffer[bidx] = ("error", payload, None)
            if slot is not None:
                self._free_slots.append(slot)
                self._push_next()

    # -- iteration -------------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._rcvd_idx == self._sent_idx or self._closed:
            self.close()
            raise StopIteration
        idx = self._rcvd_idx
        if self._pool is not None:
            kind, fut, samples = self._data_buffer.pop(idx)
            self._rcvd_idx += 1
            self._push_next()
            try:
                out = fut.result(timeout=self._data_timeout)
            except _FutTimeout as err:
                self.close(wait=False)
                self._note_timeout(idx)
                raise DataLoaderWorkerError(
                    f"DataLoader worker thread hung on batch {idx} "
                    f"(sample indices {list(samples)}): no result "
                    f"within MXTPU_DATA_TIMEOUT="
                    f"{self._data_timeout}s") from err
            except Exception as err:
                self.close()
                raise DataLoaderWorkerError(
                    f"DataLoader worker failed on batch {idx} (sample "
                    f"indices {list(samples)}): {err!r}") from err
        else:
            self._recv_until(idx)
            kind, out, _ = self._data_buffer.pop(idx)
            self._rcvd_idx += 1
            if kind == "error":
                exc_repr, tb, samples = out
                self.close()
                raise DataLoaderWorkerError(
                    f"DataLoader worker failed on batch {idx} (sample "
                    f"indices {samples}): {exc_repr}\n"
                    f"--- worker traceback ---\n{tb}")
        if isinstance(out, _np.ndarray) or (
                isinstance(out, list)
                and out and isinstance(out[0], _np.ndarray)):
            # worker transports host numpy; one device_put per array here
            return _wrap_device(out)
        return out

    # -- cleanup ---------------------------------------------------------------

    @staticmethod
    def _note_timeout(idx):
        _telemetry.event("data_worker_timeout", batch=int(idx))

    def close(self, wait=True):
        """Cancel pending work and release threads/processes/queues.
        ``wait=False`` (the hung-worker watchdog path) skips blocking
        joins — waiting on the very worker that just timed out would
        turn the watchdog into the hang it exists to break."""
        if self._closed:
            return
        self._closed = True
        if self._pool is not None:
            self._pool.shutdown(wait=wait, cancel_futures=True)
        for _ in self._procs:
            try:
                self._task_q.put(None)
            except (ValueError, OSError):
                pass
        for p in self._procs:
            p.join(timeout=5 if wait else 0.1)
            if p.is_alive():
                p.terminate()
                p.join(timeout=1)
        if self._procs:
            for q in (self._task_q, self._result_q):
                q.cancel_join_thread()
                q.close()
        self._data_buffer.clear()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class _ResumableIter:
    """Delivery-time sample accounting + quarantine-honoring replay.

    Wraps the transport iterator (same-process generator or
    `_MultiWorkerIter`) of a seeded DataLoader.  The batch *plan* — the
    submission-ordered stream of index batches — is generated lazily
    from the `ResumableSampler` and tagged with each batch's global
    ordinal; quarantined ordinals are dropped from the plan (never
    fetched — a poisoned batch must not be decoded, let alone trained
    on).  The shared `DataPipelineState` advances only when a batch is
    actually DELIVERED here (prefetched-but-undelivered work is
    invisible to a checkpoint), with any preceding quarantine skips
    accounted — and announced via one ``batch_quarantined`` telemetry
    event each — in exact delivery order.

    A wrapper that prefetches FURTHER downstream (`DevicePrefetcher`)
    calls ``defer_accounting()``: each delivery then queues a commit
    *token* instead of applying it, and the wrapper commits the token
    when the batch finally reaches ITS consumer — so the state is
    delivery-exact at the outermost layer, and tokens for batches a
    teardown discards are simply never committed.
    """

    def __init__(self, loader):
        self._loader = loader
        self._state = loader._state
        # submission-ordered ("skip"|"deliver", ordinal, n_samples)
        # events, drained in delivery order by _drain()
        self._events = collections.deque()
        self._inner = loader._raw_iter(self._plan())
        self._done = False
        self._deferred = False
        self._tokens = collections.deque()

    def _plan(self):
        st = self._state
        epoch, ordinal = st.epoch, st.batch_idx
        for batch in self._loader._batch_sampler:
            quarantined = st.is_quarantined(epoch, ordinal)
            self._events.append(
                ("skip" if quarantined else "deliver", ordinal,
                 len(batch)))
            ordinal += 1
            if not quarantined:
                yield batch

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        try:
            out = next(self._inner)
        except StopIteration:
            self._finish_epoch()
            raise
        self._settle(self._drain(stop_after_deliver=True))
        return out

    def _drain(self, stop_after_deliver):
        token = []
        while self._events:
            ev = self._events.popleft()
            token.append(ev)
            if stop_after_deliver and ev[0] == "deliver":
                break
        return token

    def _settle(self, token):
        if self._deferred:
            self._tokens.append(token)
        else:
            self.commit(token)

    def _finish_epoch(self):
        # trailing events can only be quarantine skips (every deliver
        # event precedes its batch's delivery)
        token = self._drain(stop_after_deliver=False)
        token.append(("epoch_end",))
        self._settle(token)
        self._done = True

    # -- deferred accounting (DevicePrefetcher) --------------------------------

    def defer_accounting(self):
        """Queue commit tokens instead of applying them: the caller is
        prefetching ahead of the real consumer and will ``commit`` each
        token at downstream delivery time."""
        self._deferred = True
        return self

    def take_token(self):
        return self._tokens.popleft() if self._tokens else None

    def commit(self, token):
        st = self._state
        for ev in token or ():
            if ev[0] == "skip":
                _, ordinal, n = ev
                st.skip(n)
                _telemetry.event("batch_quarantined", epoch=st.epoch,
                                 batch=int(ordinal), samples=int(n))
            elif ev[0] == "deliver":
                st.advance(ev[2])
            else:   # "epoch_end"
                st.next_epoch()

    def close(self):
        close = getattr(self._inner, "close", None)
        if close is not None:
            close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
