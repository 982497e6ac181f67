"""DataSetIterator family.

TPU-native equivalent of the reference's iterator stack:
- DataSetIterator protocol (ND4J API type, used by MultiLayerNetwork.fit —
  MultiLayerNetwork.java:978)
- AsyncDataSetIterator (reference: datasets/iterator/AsyncDataSetIterator.java:36
  — background prefetch thread; here the thread stages the *next* batch to
  device while the current step runs, overlapping host->HBM DMA with compute)
- ListDataSetIterator, IteratorDataSetIterator, MultipleEpochsIterator,
  SamplingDataSetIterator (reference: datasets/iterator/*.java)
"""
from __future__ import annotations

import queue
import threading

import numpy as np

from .dataset import DataSet


def _apply_pre(pre, ds):
    """Run one pre-processor (normalizer / callable / CombinedPreProcessor)
    on a SHALLOW COPY of the batch: normalizer transforms rebind
    ds.features, and cached-batch iterators (ListDataSetIterator,
    ExistingDataSetIterator's replay cache) hand out the same DataSet
    objects every epoch — transforming in place would silently
    double-normalize from epoch 2 on."""
    if pre is None:
        return ds
    ds = ds.shallow_copy()
    out = pre.pre_process(ds) if hasattr(pre, "pre_process") else pre(ds)
    return ds if out is None else out


def next_processed(it):
    """Pull the next batch through the iterator's pre-processor-applying
    path when it has one (DataSetIterator.next()); duck-typed iterators
    without next() fall back to raw next_batch(). ALL framework training/
    eval loops use this, so set_pre_processor works regardless of which
    iterator implementation feeds them."""
    nxt = getattr(it, "next", None)
    return nxt() if callable(nxt) else it.next_batch()


def wrap_async_for_fit(it, compute_dtype, queue_size=2):
    """fit()'s auto-wrap policy, shared by MultiLayerNetwork and
    ComputationGraph: async prefetch (queue `queue_size` — the fused
    multi-step fit loops deepen it to K+1 so a whole super-batch stages
    while the previous dispatch runs), and for bf16 models a bf16
    FEATURE wire — bit-identical training (the fused step casts features
    to bf16 anyway) with labels/masks kept at full precision."""
    import jax.numpy as jnp
    if isinstance(it, AsyncDataSetIterator):
        return it
    wire = "bfloat16" if compute_dtype == jnp.bfloat16 else None
    return AsyncDataSetIterator(it, queue_size=max(2, int(queue_size)),
                                transfer_dtype=wire, cast_labels=False)


class BatchValidationError(ValueError):
    """A batch failed DataSetValidator checks under the 'raise' policy."""


def inject_features(injector, site, ds):
    """The ONE payload-corruption seam shared by DataSetValidator and
    ParallelWrapper: fire `site` with the batch's (first) feature array
    as the payload; when a planned `corrupt` rule hands back a poisoned
    COPY, rebind it onto a shallow copy of the DataSet (the cached
    source batch is never mutated — the rebind-only contract)."""
    if injector is None:
        return ds
    feats = ds.features
    multi = isinstance(feats, (list, tuple))
    arr = feats[0] if multi else feats
    out = injector.fire(site, payload=arr)
    if out is arr:
        return ds
    ds = ds.shallow_copy()
    ds.features = [out] + list(feats[1:]) if multi else out
    return ds


class DataSetValidator:
    """Batch validation at the iterator boundary: shape/dtype/finiteness
    checks with a configurable corrupt-record policy.

    policy: 'raise' (fail the run loudly — the default, matching the
    fail-fast posture of the checkpoint loader), 'skip' (drop the bad
    batch from the stream and count it), or 'count' (let it through but
    count it — for runs that rely on the training-health watchdog's
    on-device skip instead).

    Checks (all optional except presence/alignment):
      * features present, features/labels leading dims agree;
      * `feature_shape` / `label_shape`: expected trailing (per-example)
        dims;
      * `dtypes`: allowed numpy dtype KINDS for features (e.g. "fiub");
      * `check_finite`: every float array (features, labels, masks) is
        NaN/Inf-free.

    `fault_injector` exposes the named site "data.batch" on every batch's
    features BEFORE validation — a planned `corrupt` rule NaN/Inf/value-
    poisons a COPY (rebound on a shallow copy of the DataSet, never
    mutating the cached source), making data faults injectable exactly
    like network faults. `health_policy` (a
    `common.health.TrainingHealthPolicy`) aggregates rejects into the
    run-health counters the UI shows.

    Works standalone (`validate`), wrapped (`ValidatingDataSetIterator`),
    or through the async staging path (`AsyncDataSetIterator(...,
    validator=...)` — validation runs on the prefetch thread, and a
    'skip'-rejected batch never reaches the staging queue)."""

    def __init__(self, policy="raise", check_finite=True,
                 feature_shape=None, label_shape=None, dtypes=None,
                 fault_injector=None, site="data.batch",
                 health_policy=None):
        if policy not in ("raise", "skip", "count"):
            raise ValueError(f"policy must be raise/skip/count, "
                             f"got {policy!r}")
        self.policy = policy
        self.check_finite = bool(check_finite)
        self.feature_shape = (None if feature_shape is None
                              else tuple(feature_shape))
        self.label_shape = (None if label_shape is None
                            else tuple(label_shape))
        self.dtypes = dtypes            # allowed numpy dtype kinds, e.g. "f"
        self.fault_injector = fault_injector
        self.site = site
        self.health_policy = health_policy
        # counters are mutated from the async staging pool's threads
        # (num_workers > 1 validates batches concurrently) — guarded so
        # the run-health numbers the UI shows don't lose increments
        self._lock = threading.Lock()
        self.rejected = 0
        self.passed = 0
        self.last_error = None

    # -- the checks -----------------------------------------------------
    def _problem(self, ds):
        feats, labs = ds.features, getattr(ds, "labels", None)
        if feats is None:
            return "batch has no features"
        flist = list(feats) if isinstance(feats, (list, tuple)) else [feats]
        llist = (list(labs) if isinstance(labs, (list, tuple))
                 else ([labs] if labs is not None else []))
        n = np.asarray(flist[0]).shape[0] if np.asarray(flist[0]).ndim else 0
        for a in flist:
            a = np.asarray(a)
            if a.ndim == 0 or a.shape[0] != n:
                return (f"feature batch dims disagree: {a.shape} vs "
                        f"leading {n}")
            if self.dtypes is not None and a.dtype.kind not in self.dtypes:
                return (f"feature dtype {a.dtype} not in allowed kinds "
                        f"{self.dtypes!r}")
            if (self.feature_shape is not None
                    and tuple(a.shape[1:]) != self.feature_shape):
                return (f"feature shape {tuple(a.shape[1:])} != expected "
                        f"{self.feature_shape}")
        for a in llist:
            if a is None:
                continue
            a = np.asarray(a)
            if a.ndim == 0 or a.shape[0] != n:
                return (f"label batch size {a.shape} disagrees with "
                        f"features ({n})")
            if (self.label_shape is not None
                    and tuple(a.shape[1:]) != self.label_shape):
                return (f"label shape {tuple(a.shape[1:])} != expected "
                        f"{self.label_shape}")
        if self.check_finite:
            masks = [getattr(ds, k, None) for k in
                     ("features_mask", "labels_mask")]
            for mk in ("features_masks", "labels_masks"):
                ms = getattr(ds, mk, None)
                if ms:
                    masks.extend(ms)
            for a in flist + llist + masks:
                if a is None:
                    continue
                a = np.asarray(a)
                if a.dtype.kind == "f" and not np.isfinite(a).all():
                    bad = int(a.size - np.isfinite(a).sum())
                    return f"non-finite values in batch ({bad} elements)"
        return None

    def validate(self, ds, batch_index=None):
        """Returns the (possibly injector-poisoned) batch, or None when
        the batch was rejected under the 'skip' policy. Raises
        BatchValidationError under 'raise'."""
        ds = inject_features(self.fault_injector, self.site, ds)
        problem = self._problem(ds)
        if problem is None:
            with self._lock:
                self.passed += 1
            return ds
        with self._lock:
            self.rejected += 1
            self.last_error = problem
        if self.health_policy is not None:
            self.health_policy.record_validation_reject(
                problem, batch_index=batch_index)
        if self.policy == "raise":
            raise BatchValidationError(
                f"corrupt batch rejected: {problem}")
        if self.policy == "skip":
            return None
        return ds                       # 'count': pass through, counted


def _carry_metas(src, dst):
    """Per-example metadata (DataSet.example_metas — the Prediction
    error-analysis channel) must survive every batch rebuild in the
    staging pipeline, or evaluate(meta=...) silently loses it."""
    metas = getattr(src, "example_metas", None)
    if metas is not None:
        dst.example_metas = metas
    return dst


def _wire_caster(transfer_dtype):
    """Array cast for the host->device wire: floats shrink to
    transfer_dtype (lossless-for-training at bf16); ints (uint8 pixels,
    token ids) and bool masks are already compact and pass through."""
    import jax.numpy as jnp
    dt = jnp.dtype(transfer_dtype)

    def cast(a):
        if a is None:
            return None
        arr = np.asarray(a)
        return arr.astype(dt) if arr.dtype.kind == "f" else arr

    return cast


class DataSetIterator:
    """Iterator protocol. Subclasses implement next_batch()/reset()/has_next().

    `next()` = next_batch() + the attached pre-processor (reference
    DataSetIterator.setPreProcessor semantics); all framework consumers
    (fit/eval/early-stopping loops) go through next(), so an attached
    normalizer is applied no matter which iterator subclass is used."""

    def has_next(self):
        raise NotImplementedError

    def next_batch(self):
        raise NotImplementedError

    def reset(self):
        raise NotImplementedError

    def batch(self):
        return -1

    def total_outcomes(self):
        return -1

    def input_columns(self):
        return -1

    pre_processor = None

    def set_pre_processor(self, p):
        """Attach a pre-processor applied by next().

        CONTRACT — rebind, don't mutate: a pre-processor receives a
        SHALLOW COPY of the batch (see _apply_pre) and must REBIND fields
        (``ds.features = scaled``) rather than transform arrays in place
        (``ds.features *= s``, ``np.clip(..., out=...)``). The copy shares
        the underlying arrays with the source, and cached-batch iterators
        (ListDataSetIterator, ExistingDataSetIterator's replay cache) hand
        out the same DataSet objects every epoch — an in-place write goes
        through to the cache, corrupting the stored batch and
        double-normalizing from epoch 2 on. All built-in normalizers
        rebind; custom callables must follow the same rule."""
        self.pre_processor = p
        return self

    setPreProcessor = set_pre_processor

    def next(self):
        """next_batch() with the attached pre-processor applied."""
        return _apply_pre(self.pre_processor, self.next_batch())

    # python iteration sugar
    def __iter__(self):
        self.reset()
        while self.has_next():
            yield self.next()


class ValidatingDataSetIterator(DataSetIterator):
    """Wrap any DataSetIterator with a DataSetValidator. Under the 'skip'
    policy rejected batches silently vanish from the stream (has_next
    looks ahead past them); 'raise' surfaces on next()/has_next; 'count'
    passes everything through. The underlying iterator's pre-processor
    runs FIRST (validation sees what training would see)."""

    def __init__(self, underlying, validator):
        self.underlying = underlying
        self.validator = validator
        self._pending = None
        self._index = 0

    def _advance(self):
        while self._pending is None and self.underlying.has_next():
            ds = self.validator.validate(next_processed(self.underlying),
                                         batch_index=self._index)
            self._index += 1
            if ds is not None:
                self._pending = ds

    def has_next(self):
        self._advance()
        return self._pending is not None

    def next_batch(self):
        self._advance()
        if self._pending is None:
            raise StopIteration("iterator exhausted")
        b, self._pending = self._pending, None
        return b

    def reset(self):
        self.underlying.reset()
        self._pending = None
        self._index = 0

    def batch(self):
        return self.underlying.batch()

    def total_outcomes(self):
        return self.underlying.total_outcomes()


class FileDataSetIterator(DataSetIterator):
    """Streams DataSets saved with DataSet.save() from disk, one file per
    batch — the read side of the Export training approach (reference:
    spark/iterator/PathSparkDataSetIterator streaming exported files).
    Only one batch is resident at a time."""

    def __init__(self, paths):
        self.paths = [str(p) for p in paths]
        self._i = 0

    def has_next(self):
        return self._i < len(self.paths)

    def next_batch(self):
        from .dataset import DataSet
        ds = DataSet.load(self.paths[self._i])
        self._i += 1
        return ds

    def reset(self):
        self._i = 0


class ListDataSetIterator(DataSetIterator):
    """Iterate over a list of pre-batched DataSets (reference:
    datasets/iterator/impl/ListDataSetIterator.java)."""

    def __init__(self, dataset_or_list, batch_size=None):
        if isinstance(dataset_or_list, DataSet):
            if batch_size is None:
                batch_size = dataset_or_list.num_examples()
            self._batches = list(dataset_or_list.batch_by(batch_size))
        else:
            self._batches = list(dataset_or_list)
        self._batch_size = batch_size or (
            self._batches[0].num_examples() if self._batches else 0)
        self._pos = 0

    def has_next(self):
        return self._pos < len(self._batches)

    def next_batch(self):
        b = self._batches[self._pos]
        self._pos += 1
        return b

    def reset(self):
        self._pos = 0

    def batch(self):
        return self._batch_size

    def total_outcomes(self):
        b = self._batches[0]
        return int(b.labels.shape[-1]) if b.labels is not None else -1


class IteratorDataSetIterator(DataSetIterator):
    """Wrap a python iterable of DataSets (reference:
    datasets/iterator/IteratorDataSetIterator.java)."""

    def __init__(self, make_iter):
        self._make = make_iter if callable(make_iter) else (lambda: iter(list(make_iter)))
        self._it = self._make()
        self._next = None
        self._advance()

    def _advance(self):
        try:
            self._next = next(self._it)
        except StopIteration:
            self._next = None

    def has_next(self):
        return self._next is not None

    def next_batch(self):
        b = self._next
        self._advance()
        return b

    def reset(self):
        self._it = self._make()
        self._advance()


class MultipleEpochsIterator(DataSetIterator):
    """Repeat an underlying iterator N epochs (reference:
    datasets/iterator/MultipleEpochsIterator.java)."""

    def __init__(self, num_epochs, underlying):
        self.num_epochs = int(num_epochs)
        self.underlying = underlying
        self._epoch = 0

    def has_next(self):
        if self.underlying.has_next():
            return True
        if self._epoch + 1 < self.num_epochs:
            self._epoch += 1
            self.underlying.reset()
            return self.underlying.has_next()
        return False

    def next_batch(self):
        # through the underlying's pre-processor-applying path, so a
        # normalizer attached to the inner iterator survives the wrap
        return next_processed(self.underlying)

    def reset(self):
        self._epoch = 0
        self.underlying.reset()

    def batch(self):
        return self.underlying.batch()


class SamplingDataSetIterator(DataSetIterator):
    """Random-with-replacement sampling from a DataSet (reference:
    datasets/iterator/SamplingDataSetIterator.java)."""

    def __init__(self, dataset, batch_size, total_samples, seed=42):
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.total = int(total_samples)
        self._seed = seed
        self._rng = np.random.default_rng(seed)
        self._emitted = 0

    def has_next(self):
        return self._emitted < self.total

    def next_batch(self):
        n = self.dataset.num_examples()
        idx = self._rng.integers(0, n, size=self.batch_size)
        self._emitted += self.batch_size
        return DataSet(self.dataset.features[idx],
                       self.dataset.labels[idx] if self.dataset.labels is not None else None)

    def reset(self):
        self._emitted = 0
        self._rng = np.random.default_rng(self._seed)

    def batch(self):
        return self.batch_size


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch, the host side of the TPU input pipeline.

    reference: datasets/iterator/AsyncDataSetIterator.java:36 (queue capacity
    `queueSize`, prefetch thread pinned to consumer device :75-76). Here the
    prefetch thread also calls `device_put` on the batch so host->HBM transfer
    overlaps the previous training step (double buffering); device pinning is
    implicit in jax's default device. The underlying iterator's attached
    pre-processor runs on the prefetch thread, like the reference's.

    Two wire-bytes levers for the host->HBM hop (a float32 224x224x3
    batch of 128 is 77 MB/step):

    * ``transfer_dtype``: cast float32/float64 features+labels on the host
      thread to this dtype (typically ``bfloat16``) before device_put — 2x
      fewer wire bytes, exact for bf16 models whose step casts inputs anyway.
    * ``device_transform``: a jittable array->array fn applied ON DEVICE to
      the staged features (dispatched from the prefetch thread, so it also
      overlaps the step). Lets the wire carry raw uint8 pixels (4x fewer
      bytes than f32) while normalization happens on-chip, where an affine
      scale fuses into the first conv for free. Accepts a Normalizer with
      device_apply() or any callable; see Normalizer.as_device_transform().
    """

    def __init__(self, underlying, queue_size=2, device_put=True,
                 transfer_dtype=None, device_transform=None, num_workers=1,
                 cast_labels=True, validator=None):
        self.underlying = underlying
        self.queue_size = max(1, int(queue_size))
        self._device_put = device_put
        self._transfer_dtype = transfer_dtype
        # optional DataSetValidator: runs on the prefetch thread, AFTER
        # pre-processors and BEFORE the wire cast/staging — a 'skip'-
        # rejected batch never reaches the staging queue, a 'raise'
        # surfaces through the producer-error path (not a hang)
        self._validator = validator
        # cast_labels=False: shrink FEATURES only — for a bf16 model the
        # step casts features to bf16 anyway, so a bf16 feature wire is
        # BIT-IDENTICAL training; labels can matter at full precision
        # (regression targets), so the auto-enabled fit() path leaves them
        # alone and only explicit opt-in casts them
        self._cast_labels = bool(cast_labels)
        if device_transform is not None and not device_put:
            raise ValueError(
                "device_transform requires device_put=True (the transform "
                "runs on the staged device array)")
        if device_transform is not None and not callable(device_transform):
            device_transform = device_transform.as_device_transform()
        self._device_transform = device_transform
        if device_transform is not None:
            import jax
            # one shared jit object per iterator (created eagerly: no
            # lazy-init race between staging threads). A Normalizer's
            # as_device_transform() already returns a memoized JITTED
            # function — use it as-is so every iterator over the same
            # normalizer shares one compiled program (re-wrapping in
            # jax.jit would give each iterator its own executable cache)
            if hasattr(device_transform, "lower"):   # already jit-wrapped
                self._device_fn = device_transform
            else:
                self._device_fn = jax.jit(device_transform)
        else:
            self._device_fn = None
        # >1 overlaps per-batch prepare+transfer latency — for hosts where
        # per-put round-trip or host-side decode dominates. NOT a win
        # everywhere: concurrent puts contend for a serialized link (4
        # workers measured 2.5x SLOWER than 1 on the round-5 host), so
        # the default stays 1; raise it for host-bound pipelines. Batch
        # ORDER is preserved
        # regardless (futures are collected FIFO).
        self.num_workers = max(1, int(num_workers))
        self._q = None
        self._thread = None
        self._sentinel = object()
        self._start()

    def _start(self):
        self._q = queue.Queue(maxsize=self.queue_size)
        self._error = None
        self._consumed_any = False
        old_pool = getattr(self, "_pool", None)
        if old_pool is not None:
            # reset() re-runs _start() every epoch; reclaim the previous
            # epoch's staging threads instead of leaking a pool per epoch
            old_pool.shutdown(wait=False)
            self._pool = None
        # per-generation stop event: reset()/_start() signals the OLD
        # generation's threads to exit so a failed collector can't leave
        # the producer blocked on a full future queue, and a restart can't
        # race the old producer's next_batch() against underlying.reset()
        old_stop = getattr(self, "_stop", None)
        if old_stop is not None:
            old_stop.set()
        self._stop = threading.Event()
        if self.num_workers == 1:
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()
        else:
            # producer submits prepare+stage jobs to a pool; collector
            # drains the future queue FIFO so order is preserved no matter
            # which worker finishes first
            import concurrent.futures as cf
            self._pool = cf.ThreadPoolExecutor(
                max_workers=self.num_workers,
                thread_name_prefix="async-ds-stage")
            self._futs = queue.Queue(maxsize=self.queue_size
                                     + self.num_workers)
            # kept joinable: reset() must wait for an in-flight
            # next_batch() before it may touch the (non-thread-safe)
            # underlying iterator
            self._producer_thread = threading.Thread(
                target=self._producer, args=(self._futs, self._stop),
                daemon=True)
            self._producer_thread.start()
            self._thread = threading.Thread(
                target=self._collector, args=(self._futs, self._stop),
                daemon=True)
            self._thread.start()
        self._next = self._q.get()
        self._raise_if_failed()

    def _prepare(self, ds):
        """Per-batch pipeline work: pre-process (the underlying iterator's
        then this iterator's own, both on the prefetch thread like
        reference AsyncDataSetIterator), wire-cast, stage."""
        ds = _apply_pre(getattr(self.underlying, "pre_processor", None), ds)
        ds = _apply_pre(self.pre_processor, ds)
        if self._validator is not None:
            ds = self._validator.validate(ds)
            if ds is None:          # rejected under the 'skip' policy
                return None
        if self._transfer_dtype is not None:
            ds = self._cast_for_wire(ds)
        if self._device_put:
            ds = self._stage(ds)
        return ds

    def _worker(self):
        stop = self._stop      # THIS generation's stop event
        try:
            while not stop.is_set() and self.underlying.has_next():
                item = self._prepare(self.underlying.next_batch())
                if item is None:
                    continue           # validator-skipped batch
                # stop-aware put: reset() signals stop FIRST, so a
                # mid-stream reset stops staging within one batch
                # instead of preparing the whole remaining pass just to
                # drain it (the consumer-side drain keeps this live)
                while not stop.is_set():
                    try:
                        self._q.put(item, timeout=0.2)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # re-raised on the consumer thread
            self._error = e
        finally:
            self._q.put(self._sentinel)

    @staticmethod
    def _put_control(futs, stop, item):
        """Stop-aware blocking put for CONTROL items (a mid-stream
        exception, the end sentinel). A single timed attempt under a full
        queue — the steady state whenever the training step is slower than
        staging — would silently drop the item and leave the collector
        blocked on futs.get() forever, turning a data error into a hang
        (ADVICE r5). Retry until it lands or the generation stops (a dead
        collector has already drained futs and sentinel'd the consumer
        queue, so giving up on stop is safe)."""
        while not stop.is_set():
            try:
                futs.put(item, timeout=0.2)
                return
            except queue.Full:
                continue

    def _producer(self, futs, stop):
        try:
            while not stop.is_set() and self.underlying.has_next():
                # next_batch() stays on ONE thread (iterators aren't
                # thread-safe); only prepare/stage fans out
                ds = self.underlying.next_batch()
                fut = self._pool.submit(self._prepare, ds)
                while not stop.is_set():
                    try:
                        futs.put(fut, timeout=0.2)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surfaced by the collector
            self._put_control(futs, stop, e)
        finally:
            self._put_control(futs, stop, self._sentinel)

    def _collector(self, futs, stop):
        try:
            while not stop.is_set():
                # timed get, not a bare block: when reset() stops this
                # generation the producer may exit WITHOUT a sentinel
                # (its control put is stop-aware), and a collector parked
                # in futs.get() would never wake to deliver its own
                # sentinel — deadlocking the reset drain
                try:
                    fut = futs.get(timeout=0.2)
                except queue.Empty:
                    continue
                if fut is self._sentinel:
                    break
                if isinstance(fut, BaseException):
                    raise fut
                res = fut.result()
                if res is None:
                    continue           # validator-skipped batch
                self._q.put(res)
        except BaseException as e:
            self._error = e
            stop.set()            # unblock the producer's bounded put
            while True:           # drain so its in-flight put releases
                try:
                    futs.get_nowait()
                except queue.Empty:
                    break
        finally:
            self._q.put(self._sentinel)

    def _cast_for_wire(self, ds):
        from .dataset import MultiDataSet
        if isinstance(ds, MultiDataSet):
            # a plain DataSetIterator can legally yield MultiDataSets
            # (ExistingDataSetIterator over a MultiDataSet list feeding
            # ComputationGraph.fit) — dispatch per batch type
            return AsyncMultiDataSetIterator._cast_for_wire(self, ds)
        cast = _wire_caster(self._transfer_dtype)
        keep = (lambda a: a) if not self._cast_labels else cast
        out = DataSet.__new__(DataSet)
        out.features = cast(ds.features)
        out.labels = keep(ds.labels)
        out.features_mask = keep(ds.features_mask)
        out.labels_mask = keep(ds.labels_mask)
        _carry_metas(ds, out)
        return out

    def _raise_if_failed(self):
        if self._next is self._sentinel and self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError("prefetch worker failed") from err

    def _stage(self, ds):
        import jax

        from .dataset import MultiDataSet
        if isinstance(ds, MultiDataSet):
            return AsyncMultiDataSetIterator._stage(self, ds)
        staged = DataSet.__new__(DataSet)
        staged.features = jax.device_put(ds.features)
        if self._device_fn is not None:
            # dispatched (async) from the prefetch thread: the on-chip
            # normalize overlaps the current training step like the
            # transfer does
            staged.features = self._device_fn(staged.features)
        staged.labels = (jax.device_put(ds.labels)
                         if ds.labels is not None else None)
        staged.features_mask = (jax.device_put(ds.features_mask)
                                if ds.features_mask is not None else None)
        staged.labels_mask = (jax.device_put(ds.labels_mask)
                              if ds.labels_mask is not None else None)
        _carry_metas(ds, staged)
        return staged

    def has_next(self):
        self._raise_if_failed()
        return self._next is not self._sentinel

    def next_batch(self):
        b = self._next
        if b is self._sentinel:
            self._raise_if_failed()
            raise StopIteration("iterator exhausted")
        self._consumed_any = True
        self._next = self._q.get()
        # staging-queue depth AFTER the take: the pipeline-health gauge
        # (0 here while the fit loop is fast means the loop is
        # DATA-starved; full means compute-bound — the two regimes the
        # async-overlap test distinguishes). Published on the shared
        # registry so /metrics and obs_report show it next to dispatch
        # spans. AsyncMultiDataSetIterator inherits this path. The
        # gauge/counter resolve ONCE (first batch) — per-batch cost is
        # an attribute load + the counter's own lock, never the
        # registry lock.
        obs = getattr(self, "_obs_metrics", None)
        if obs is None:
            from ..obs.registry import default_registry
            reg = default_registry()
            obs = self._obs_metrics = (
                reg.gauge("data.async_iterator.queue_depth"),
                reg.counter("data.async_iterator.batches"))
        obs[0].set(self._q.qsize())
        obs[1].inc()
        return b

    def next(self):
        # pre-processors (underlying's and this iterator's own) already ran
        # on the prefetch thread in _prepare(); re-applying here would
        # double-normalize
        return self.next_batch()

    def __iter__(self):
        # a FRESH wrapper is already prefetching from position 0; the base
        # reset-first iteration protocol would drain one fully-staged pass
        # unseen. Only reset when batches were consumed (mid-stream rewind)
        # or the stream is exhausted (re-iteration).
        if self._consumed_any or not self.has_next():
            self.reset()
        while self.has_next():
            yield self.next()

    def set_pre_processor(self, p):
        # the prefetch worker started in __init__ and has already prepared
        # up to queue_size+2 batches with the OLD (absent) pre-processor —
        # attaching now would silently train the first batches raw.
        # Attach to the underlying iterator BEFORE wrapping instead (the
        # worker applies it), or pass it at construction time.
        raise RuntimeError(
            "set_pre_processor on a running AsyncDataSetIterator would "
            "miss already-prefetched batches; attach the pre-processor "
            "to the underlying iterator before wrapping")

    def reset(self):
        # signal the CURRENT generation to stop producing BEFORE draining:
        # without it the drain consumes (and stages — pre-process +
        # device_put, the expensive part) every remaining batch just to
        # reach the sentinel; with it, at most the in-flight batches are
        # discarded. The consumer-side drain keeps the producer's final
        # puts live until its sentinel lands.
        stop = getattr(self, "_stop", None)
        if stop is not None:
            stop.set()
        while self._next is not self._sentinel:
            self._next = self._q.get()
        # multi-worker: the stop-aware producer may still be INSIDE
        # underlying.next_batch() when the (collector-sentinelled) drain
        # completes — join it before resetting the non-thread-safe
        # underlying iterator. Single-worker needs no join: its sentinel
        # only appears after its loop left the underlying for good.
        pt = getattr(self, "_producer_thread", None)
        if pt is not None and pt.is_alive():
            pt.join()
        self.underlying.reset()
        self._start()

    def batch(self):
        return self.underlying.batch()


class AsyncMultiDataSetIterator(AsyncDataSetIterator):
    """Background prefetch of MultiDataSets for ComputationGraph training.
    reference: datasets/iterator/AsyncMultiDataSetIterator.java — same
    queue/thread contract as the DataSet variant, staging every input/output
    array (and masks) to the device off the training thread."""

    def _cast_for_wire(self, mds):
        from .dataset import MultiDataSet
        cast = _wire_caster(self._transfer_dtype)
        keep = (lambda a: a) if not self._cast_labels else cast
        out = MultiDataSet.__new__(MultiDataSet)
        out.features = [cast(f) for f in mds.features]
        out.labels = [keep(l) for l in mds.labels]
        out.features_masks = ([keep(m) for m in mds.features_masks]
                              if mds.features_masks else mds.features_masks)
        out.labels_masks = ([keep(m) for m in mds.labels_masks]
                            if mds.labels_masks else mds.labels_masks)
        # symmetric with the DataSet wire path: per-example metadata must
        # survive the bf16-wire rebuild too (ADVICE r5)
        _carry_metas(mds, out)
        return out

    def _stage(self, mds):
        import jax

        from .dataset import MultiDataSet
        put = jax.device_put
        staged = MultiDataSet.__new__(MultiDataSet)
        staged.features = [put(f) for f in mds.features]
        if self._device_fn is not None:
            staged.features = [self._device_fn(f) for f in staged.features]
        staged.labels = [put(l) for l in mds.labels]
        staged.features_masks = ([put(m) if m is not None else None
                                  for m in mds.features_masks]
                                 if mds.features_masks else
                                 mds.features_masks)
        staged.labels_masks = ([put(m) if m is not None else None
                                for m in mds.labels_masks]
                               if mds.labels_masks else mds.labels_masks)
        _carry_metas(mds, staged)
        return staged


class ExistingDataSetIterator(DataSetIterator):
    """Adapt any python iterable of DataSets (or a factory callable) to the
    DataSetIterator protocol. reference:
    datasets/iterator/ExistingDataSetIterator.java (wraps an
    Iterable<DataSet> so reset() restarts it).

    One-shot sources (generators) are replayed from a cache on reset():
    a bare generator cannot be restarted, and re-calling iter() on it
    would silently drop already-prefetched batches."""

    def __init__(self, iterable_or_factory, total_outcomes=-1):
        self._source = iterable_or_factory
        self._outcomes = int(total_outcomes)
        src = iterable_or_factory
        self._one_shot = (not callable(src)) and iter(src) is src
        if self._one_shot:
            self._consumed = []   # every item ever pulled from the source
            self._pos = 0
        self.reset()

    def reset(self):
        if self._one_shot:
            self._pos = 0
            return
        src = self._source
        self._it = iter(src() if callable(src) else src)
        self._next = next(self._it, None)

    def has_next(self):
        if self._one_shot:
            if self._pos < len(self._consumed):
                return True
            try:
                self._consumed.append(next(self._source))
                return True
            except StopIteration:
                return False
        return self._next is not None

    def next_batch(self):
        if self._one_shot:
            if not self.has_next():
                return None
            ds = self._consumed[self._pos]
            self._pos += 1
            return ds
        ds = self._next
        self._next = next(self._it, None)
        return ds

    def total_outcomes(self):
        return self._outcomes


class ArraysDataSetIterator(DataSetIterator):
    """Batches over (features, labels) array pairs — reference
    INDArrayDataSetIterator.java / DoublesDataSetIterator.java /
    FloatsDataSetIterator.java collapse to one class here (numpy carries
    the dtype; the reference needed one wrapper per java primitive)."""

    def __init__(self, pairs, batch_size):
        """pairs: iterable of (features_row, labels_row) examples, or a
        single (features, labels) array tuple."""
        if (isinstance(pairs, tuple) and len(pairs) == 2
                and hasattr(pairs[0], "shape")):
            feats, labs = pairs
        else:
            pairs = list(pairs)
            feats = np.stack([np.asarray(f, np.float32) for f, _ in pairs])
            labs = np.stack([np.asarray(l, np.float32) for _, l in pairs])
        self._ds = DataSet(np.asarray(feats), np.asarray(labs))
        self.batch_size = int(batch_size)
        self.reset()

    def reset(self):
        self._pos = 0

    def has_next(self):
        return self._pos < self._ds.num_examples()

    def next_batch(self):
        i, j = self._pos, self._pos + self.batch_size
        self._pos = j
        return DataSet(self._ds.features[i:j], self._ds.labels[i:j])

    def batch(self):
        return self.batch_size

    def input_columns(self):
        return int(np.prod(self._ds.features.shape[1:]))

    def total_outcomes(self):
        return int(self._ds.labels.shape[-1])


INDArrayDataSetIterator = ArraysDataSetIterator   # reference names
DoublesDataSetIterator = ArraysDataSetIterator
FloatsDataSetIterator = ArraysDataSetIterator


class ReconstructionDataSetIterator(DataSetIterator):
    """Wrap an iterator, replacing labels with the features (autoencoder
    targets). reference: datasets/iterator/ReconstructionDataSetIterator.java."""

    def __init__(self, backing):
        self.backing = backing

    def has_next(self):
        return self.backing.has_next()

    def next_batch(self):
        ds = self.backing.next_batch()
        return DataSet(ds.features, ds.features,
                       ds.features_mask, ds.features_mask)

    def reset(self):
        self.backing.reset()

    def batch(self):
        return self.backing.batch()

    def input_columns(self):
        return self.backing.input_columns()

    def total_outcomes(self):
        return self.backing.input_columns()


class MovingWindowDataSetIterator(DataSetIterator):
    """Sliding windows over a sequence dataset: each batch element is a
    [window, features] slice advanced by `stride`. reference:
    datasets/iterator/MovingWindowBaseDataSetIterator.java (2-D moving
    window over matrices)."""

    def __init__(self, features, labels, window, stride=1, batch_size=32):
        feats = np.asarray(features)
        labs = np.asarray(labels)
        xs, ys = [], []
        for start in range(0, len(feats) - window + 1, int(stride)):
            xs.append(feats[start:start + window])
            ys.append(labs[start + window - 1])
        self._x = np.stack(xs) if xs else np.zeros((0, window) +
                                                   feats.shape[1:])
        self._y = np.stack(ys) if ys else np.zeros((0,) + labs.shape[1:])
        self.batch_size = int(batch_size)
        self.reset()

    def reset(self):
        self._pos = 0

    def has_next(self):
        return self._pos < len(self._x)

    def next_batch(self):
        i, j = self._pos, self._pos + self.batch_size
        self._pos = j
        return DataSet(self._x[i:j], self._y[i:j])

    def batch(self):
        return self.batch_size


class CombinedPreProcessor:
    """Chain DataSet pre-processors — reference
    datasets/iterator/CombinedPreProcessor.java (Builder.addPreProcessor).
    A pre-processor is any object with pre_process(ds) (normalizers
    qualify).

    Every step is bound by the same rebind-only contract as
    `DataSetIterator.set_pre_processor`: transform by REBINDING fields on
    the DataSet it receives (or returning a new DataSet), never by
    mutating the arrays in place — the chain runs on a shallow copy whose
    arrays are shared with the iterator's (possibly cached) source batch,
    so an in-place write corrupts replayed epochs."""

    class Builder:
        def __init__(self):
            self._steps = []

        def add_pre_processor(self, p):
            self._steps.append(p); return self

        addPreProcessor = add_pre_processor

        def build(self):
            return CombinedPreProcessor(self._steps)

    def __init__(self, steps):
        self.steps = list(steps)

    def pre_process(self, ds):
        for p in self.steps:
            out = p.pre_process(ds) if hasattr(p, "pre_process") else p(ds)
            if out is not None:
                ds = out
        return ds

    preProcess = pre_process
