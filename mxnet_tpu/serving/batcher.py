"""Continuous batcher: coalesce concurrent requests into bucketed groups.

One daemon thread owns the engine.  Submitters get a
``concurrent.futures.Future`` back immediately; the loop collects
requests until either the latency deadline (``MXTPU_SERVE_MAX_DELAY_MS``
past the FIRST queued request — later arrivals don't extend it) or the
largest batch bucket is reached, serves the group through ONE bucketed
AOT dispatch sequence, and resolves every future.

The deadline is the latency/throughput dial: 0 serves each request the
moment the engine is free (lowest latency, no coalescing); a few ms lets
concurrent clients share a prefill+decode pass (the padded rows are
nearly free, so tokens/sec scales with the bucket fill).

``before_batch`` runs between groups with the engine idle — the hook
serving/replica.py uses to hot-swap reloaded weights with zero dropped
requests.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import Future

from .. import telemetry
from ..base import MXNetError, getenv_int
from ..obs.spans import Trace
from ..profiler import scope


class ServerOverloaded(MXNetError):
    """submit() on a full admission queue: the request is shed
    immediately instead of growing tail latency unboundedly."""


class DeadlineExceeded(MXNetError):
    """The request's deadline passed before it reached the engine."""


def max_delay_ms_from_env(default=5.0):
    raw = os.environ.get("MXTPU_SERVE_MAX_DELAY_MS")
    if not raw:
        return default
    try:
        return max(0.0, float(raw))
    except ValueError:
        return default


def max_queue_from_env(default=256):
    return max(1, getenv_int("MXTPU_SERVE_MAX_QUEUE", default))


_SHUTDOWN = object()    # close() sentinel: wakes the blocked collector


class _Request:
    __slots__ = ("prompt", "max_new_tokens", "future", "t_enqueue",
                 "deadline", "trace", "span", "qspan")

    def __init__(self, prompt, max_new_tokens, deadline_ms=None,
                 trace=None, replica_id=None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.future = Future()
        self.t_enqueue = time.perf_counter()
        self.deadline = (None if deadline_ms is None
                         else self.t_enqueue + float(deadline_ms) / 1e3)
        # span tree (obs/spans.py): a FrontDoor-minted trace arrives
        # with an open root; a direct submit roots at the batcher
        t_wall = time.time()
        if trace is None:
            trace = Trace()
            self.span = trace.begin("batcher", t0=t_wall,
                                    replica_id=replica_id)
        else:
            self.span = trace.begin("batcher", parent=trace.root(),
                                    t0=t_wall, replica_id=replica_id)
        self.trace = trace
        self.qspan = trace.begin("queue", parent=self.span, t0=t_wall)


class ContinuousBatcher:
    """Queue + serving loop over a ServingEngine.

    ``submit(prompt, max_new_tokens)`` → Future resolving to a dict:
    ``tokens`` (np.int32 generated ids) plus the per-request record
    fields (queue_us, prefill_us, decode_us_per_token, bucket,
    padded_fraction, generation).
    """

    def __init__(self, engine, max_delay_ms=None, max_batch=None,
                 before_batch=None, temperature=None, rng=None,
                 max_queue=None, replica_id=None):
        self.engine = engine
        self.replica_id = replica_id
        self.max_delay_ms = (max_delay_ms_from_env()
                             if max_delay_ms is None else max_delay_ms)
        self.max_batch = max_batch or max(engine.batch_buckets)
        self.max_queue = (max_queue_from_env()
                          if max_queue is None else max(1, int(max_queue)))
        self.before_batch = before_batch
        self._temperature = temperature
        self._rng = rng
        self._q = queue.Queue(maxsize=self.max_queue)
        self._stop = threading.Event()
        self.groups_served = 0
        self.requests_served = 0
        self.shed = 0
        self.deadline_exceeded = 0
        self._thread = threading.Thread(target=self._loop,
                                        name="mxtpu-batcher", daemon=True)
        self._thread.start()

    def submit(self, prompt, max_new_tokens=16, deadline_ms=None,
               trace=None):
        """Enqueue one request → Future.  Raises
        :class:`ServerOverloaded` when the admission queue is full (the
        caller — or its FrontDoor — decides whether to retry elsewhere);
        a ``deadline_ms`` budget resolves the future with
        :class:`DeadlineExceeded` if group formation can't reach it in
        time.  ``trace``: an obs.spans.Trace minted upstream (the
        FrontDoor) — batcher/prefill/decode spans attach under its
        root; None mints a batcher-rooted trace."""
        if self._stop.is_set():
            raise RuntimeError("batcher is closed")
        req = _Request(prompt, max_new_tokens, deadline_ms,
                       trace=trace, replica_id=self.replica_id)
        try:
            self._q.put_nowait(req)
        except queue.Full:
            self.shed += 1
            telemetry.count("serving.queue_full")
            telemetry.event("queue_full", depth=self.max_queue)
            raise ServerOverloaded(
                f"serving queue full ({self.max_queue} pending); "
                f"request shed") from None
        return req.future

    def _collect(self):
        """Block for the first request, then coalesce until the deadline
        or the largest bucket fills.  Blocking (not polling): an idle
        replica costs zero CPU; close() wakes the block with a
        sentinel — _collect returns no group and the loop exits to
        drain.  Returns (group, microseconds under ``serve.collect``)."""
        first = self._q.get()
        if first is _SHUTDOWN:
            return None, 0.0
        group = [first]
        deadline = first.t_enqueue + self.max_delay_ms / 1e3
        # the span runs from the first request dequeued to the group
        # closed: the device idles here while clients (re)submit
        with scope("serve.collect") as sp:
            while len(group) < self.max_batch:
                wait = deadline - time.perf_counter()
                if wait <= 0:
                    # deadline hit — grab whatever is already queued,
                    # no wait
                    try:
                        while len(group) < self.max_batch:
                            item = self._q.get_nowait()
                            if item is _SHUTDOWN:
                                break   # _loop re-checks _stop next
                            group.append(item)
                    except queue.Empty:
                        pass
                    break
                try:
                    item = self._q.get(timeout=wait)
                except queue.Empty:
                    break
                if item is _SHUTDOWN:
                    break
                group.append(item)
            sp.set(n=len(group))
        return group, (sp.t1 - sp.t0) * 1e6

    def _expire(self, group, now):
        """Resolve requests whose deadline passed during queueing with
        DeadlineExceeded BEFORE they cost a dispatch slot; returns the
        still-live remainder."""
        live = []
        for r in group:
            if r.deadline is None or now <= r.deadline:
                live.append(r)
                continue
            self.deadline_exceeded += 1
            telemetry.count("serving.deadline_exceeded")
            queue_us = (now - r.t_enqueue) * 1e6
            r.qspan.close(dur_us=queue_us)
            r.span.attrs["deadline_exceeded"] = True
            r.trace.close_open()
            telemetry.request_record(
                queue_us=queue_us,
                prefill_us=0.0, decode_us_per_token=0.0,
                bucket=[1, 1], padded_fraction=0.0, new_tokens=0,
                deadline_exceeded=True, replica_id=self.replica_id,
                **r.trace.to_fields())
            if not r.future.cancelled():
                r.future.set_exception(DeadlineExceeded(
                    f"deadline passed after "
                    f"{(now - r.t_enqueue) * 1e3:.1f} ms in queue"))
        return live

    def _serve(self, group, collect_us=0.0):
        """One group, under the ``serve.group`` span: expire, serve
        (the engine's ``serve.prefill.*`` / ``serve.decode.*`` spans),
        then ``serve.finish``: records, telemetry, futures."""
        with scope("serve.group") as span:
            t_batch = span.t0
            group = self._expire(group, t_batch)
            if not group:
                return
            try:
                if self.before_batch is not None:
                    self.before_batch()
                outs, timings = self.engine.serve_group(
                    [r.prompt for r in group],
                    [r.max_new_tokens for r in group],
                    temperature=self._temperature, rng=self._rng)
            except BaseException as exc:  # resolve ALL futures, never hang
                for r in group:
                    if not r.future.cancelled():
                        r.future.set_exception(exc)
                return
            span.set(B=timings["bucket"][0], S=timings["bucket"][1],
                     steps=max(len(o) for o in outs),
                     generation=timings["generation"],
                     **{k: timings[k] for k in ("cache_bytes_reserved",
                                                "cache_bytes_written")
                        if k in timings})
            self.groups_served += 1
            self.requests_served += len(group)
            with scope("serve.finish") as fin:
                self._finish(group, outs, dict(timings,
                                               collect_us=collect_us),
                             t_batch, fin.t0)

    #: the engine's fields that every request's ``request`` record
    #: carries where the engine reports them (its counts of what the
    #: group's programs did, and the memory ledger); the family
    #: counters stay in the future's record
    _ENGINE_FIELDS = (
        "decode_host_us_per_step", "decode_steps_fed_on_device",
        "decode_readback_bytes_per_step", "decode_row_steps",
        "decode_row_steps_live", "decode_cache_write_kernel_share",
        "decode_cache_write_live_share", "decode_attn_kernel_share",
        "decode_attn_window_read_pct", "prefill_attn_kernel_share",
        "moe_grouped_kernel_share", "moe_combine_kernel_share") \
        + telemetry.MEMORY_FIELDS

    def _finish(self, group, outs, timings, t_batch, t_finish):
        t_done = time.time()
        passed_on = {k: timings[k] for k in self._ENGINE_FIELDS
                     if k in timings}
        for r, toks in zip(group, outs):
            queue_us = (t_batch - r.t_enqueue) * 1e6
            rec = dict(timings)
            rec["queue_us"] = queue_us
            rec["tokens"] = toks
            # the request's tree takes prefill and decode from the
            # engine's real spans, their start and duration; an engine
            # that reports none gets none rebuilt here (obs/spans.py)
            r.qspan.close(dur_us=queue_us)
            if "t_prefill0" in timings:
                r.trace.add("prefill", parent=r.span,
                            t0=timings["t_prefill0"],
                            dur_us=timings["prefill_us"],
                            bucket=f"{timings['bucket'][0]}x"
                                   f"{timings['bucket'][1]}",
                            generation=timings["generation"])
                r.trace.add("decode", parent=r.span,
                            t0=timings["t_decode0"],
                            dur_us=timings["decode_us"],
                            new_tokens=len(toks))
            r.trace.close_open(t_end=t_done)
            telemetry.request_record(
                queue_us=queue_us,
                prefill_us=timings["prefill_us"],
                decode_us_per_token=timings["decode_us_per_token"],
                bucket=timings["bucket"],
                padded_fraction=timings["padded_fraction"],
                new_tokens=len(toks),
                generation=timings["generation"],
                deadline_exceeded=False, replica_id=self.replica_id,
                collect_us=timings["collect_us"],
                **passed_on, **r.trace.to_fields())
            # from the group's last token to this request's answer
            # handed over: one clock read a request, none a step
            rec["finish_us"] = (time.perf_counter() - t_finish) * 1e6
            if not r.future.cancelled():
                r.future.set_result(rec)

    def _loop(self):
        while not self._stop.is_set():
            group, collect_us = self._collect()
            if group is None:
                break
            if group:
                self._serve(group, collect_us)
        # drain: resolve what is left rather than abandoning futures
        while True:
            try:
                group = [self._q.get_nowait()]
            except queue.Empty:
                break
            if group[0] is not _SHUTDOWN:
                self._serve(group)

    def close(self, timeout=30.0):
        """Stop the loop; queued requests are still served (drained)."""
        self._stop.set()
        # wake the blocked collector; the loop is consuming, so a full
        # queue clears within the timeout
        deadline = time.perf_counter() + timeout
        while self._thread.is_alive():
            try:
                self._q.put(_SHUTDOWN, timeout=0.1)
                break
            except queue.Full:
                if time.perf_counter() > deadline:
                    break
        self._thread.join(timeout)
