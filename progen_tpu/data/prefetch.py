"""Double-buffered host->device input feed.

The reference feeds the accelerator synchronously — ``next(train_dataset)``
then the jitted call, every micro-step (``/root/reference/train.py:191-193``)
— so the device idles while the host runs tf.data + the NumPy collate and
the host->device transfer.  On a 500-step run measured 2026-07-29 on one
v5e chip (before PRs 1–20; not measured on today's code) that
serialization cost ~10% of steady-state throughput
(``runs/90b685bbc4d5``: 76.7k tokens/sec fed synchronously vs 85.3k for
``bench.py`` on device-resident batches).

:class:`DevicePrefetcher` moves the feed off the critical path: a daemon
thread pulls host batches and STARTS their device transfer (JAX transfers
are async — the returned array is a future) while the current step
executes, keeping ``depth`` batches in flight.  The training loop's
``next()`` then usually returns a batch whose transfer already completed.

Thread-safety: the worker calls only ``next(iterator)`` and ``to_device``
(``jax.device_put``/``make_array_from_process_local_data``), both safe off
the main thread; all jitted-step dispatch stays on the caller's thread.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator

import numpy as np


class _End:
    pass


class _Raised:
    def __init__(self, exc: BaseException):
        self.exc = exc


class DevicePrefetcher:
    """Wrap ``iterator`` so device transfers overlap step execution.

    ``to_device``: host batch -> device array (its transfer may be async).
    ``depth``: batches buffered ahead (2 = classic double buffering; more
    only helps when the host feed is bursty).
    """

    def __init__(
        self,
        iterator: Iterator[Any],
        to_device: Callable[[Any], Any],
        depth: int = 2,
    ):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._iterator = iterator
        self._to_device = to_device
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._worker, name="progen-prefetch", daemon=True
        )
        self._thread.start()

    def _worker(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    batch = next(self._iterator)
                except StopIteration:
                    self._put(_End())
                    return
                self._put(self._to_device(batch))
        except BaseException as e:  # surfaced on the consumer thread
            self._put(_Raised(e))

    def _put(self, item) -> None:
        # bounded put that gives up when the consumer is shutting down
        # (otherwise a full queue would wedge the daemon thread forever)
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if isinstance(item, _End):
            raise StopIteration
        if isinstance(item, _Raised):
            raise item.exc
        return item

    def close(self) -> None:
        """Stop the worker and drop buffered batches (idempotent).

        The wrapped iterator is OWNED by the prefetcher from construction
        on: the worker may be blocked inside ``next(iterator)`` (e.g.
        tf.data waiting on a slow source), in which case it survives the
        bounded join as an orphaned daemon and may still consume one more
        item when the source unblocks.  Never hand the underlying iterator
        to another consumer after wrapping it."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
        if self._thread.is_alive():
            import warnings

            warnings.warn(
                "DevicePrefetcher worker did not exit within 5s (blocked in "
                "next() on the wrapped iterator?); it remains attached to "
                "the iterator and may consume one more batch",
                RuntimeWarning,
                stacklevel=2,
            )


class SuperbatchStager:
    """Stage ``(k, accum, B, L)`` superbatches for the fused multi-step
    train loop (``TrainFunctions.train_multi_step``).

    A background thread keeps up to ``depth`` supersteps' worth of host
    micro-batches pulled ahead (reusing :class:`DevicePrefetcher` with an
    identity transform as the host-side buffer); :meth:`get` stacks the
    next ``k * accum`` of them into one contiguous array and hands it to
    ``to_device`` — a JAX transfer is asynchronous, so the copy streams to
    HBM while the PREVIOUS superstep is still executing, and the returned
    array is fresh every call (safe for the step's buffer donation).

    ``k`` may vary per call (the trainer shrinks the final superstep
    before a hook boundary) up to the ``k_max`` the stager was sized for.
    """

    def __init__(
        self,
        iterator: Iterator[Any],
        to_device: Callable[[Any], Any],
        accum: int,
        k_max: int,
        depth: int = 2,
    ):
        if accum < 1:
            raise ValueError(f"accum must be >= 1, got {accum}")
        if k_max < 1:
            raise ValueError(f"k_max must be >= 1, got {k_max}")
        self._accum = accum
        self._k_max = k_max
        self._to_device = to_device
        self._host = DevicePrefetcher(
            iterator,
            lambda batch: batch,  # host-side buffering only
            depth=max(1, depth) * k_max * accum,
        )

    def get(self, k: int):
        """The next ``k`` optimizer steps' data as one ``(k, accum, B, L)``
        device array (its transfer may still be in flight — JAX arrays are
        futures).  Raises ``StopIteration`` when the wrapped iterator
        cannot supply a full superbatch (the trainer feeds a looping
        stream, so this only surfaces on finite test iterators)."""
        if not 1 <= k <= self._k_max:
            raise ValueError(f"k must be in [1, {self._k_max}], got {k}")
        need = k * self._accum
        micro = [next(self._host) for _ in range(need)]
        stacked = np.stack(micro).reshape(
            (k, self._accum) + np.shape(micro[0]))
        return self._to_device(stacked)

    def close(self) -> None:
        """Stop the host prefetch worker and drop buffered batches."""
        self._host.close()
