"""Data-pipeline tests: container format, Example codec, augmentation,
dataset iteration + mid-epoch resume, host prefetch pipeline.

TF 2.21 (installed) is used as the *oracle* for wire-format compatibility —
SURVEY.md §4.5's parity-harness strategy.
"""

import numpy as np
import pytest

from distributed_tensorflow_models_tpu.data import (
    augment,
    datasets,
    example_proto,
    pipeline,
    tfrecord,
)


# --------------------------------------------------------------------------
# TFRecord container
# --------------------------------------------------------------------------


def test_tfrecord_roundtrip(tmp_path):
    path = tmp_path / "a.tfrecord"
    payloads = [b"hello", b"", b"x" * 10_000, bytes(range(256))]
    assert tfrecord.write_records(path, payloads) == 4
    assert list(tfrecord.read_records(path)) == payloads


def test_tfrecord_crc_detects_corruption(tmp_path):
    path = tmp_path / "a.tfrecord"
    tfrecord.write_records(path, [b"payload-data"])
    raw = bytearray(path.read_bytes())
    raw[14] ^= 0xFF  # flip a payload byte
    path.write_bytes(bytes(raw))
    with pytest.raises(tfrecord.CorruptRecordError):
        list(tfrecord.read_records(path))


def test_tfrecord_matches_tf_oracle(tmp_path):
    tf = pytest.importorskip("tensorflow")
    path = str(tmp_path / "oracle.tfrecord")
    payloads = [b"first", b"second" * 100]
    with tf.io.TFRecordWriter(path) as w:
        for p in payloads:
            w.write(p)
    assert list(tfrecord.read_records(path)) == payloads
    # And TF can read ours.
    ours = str(tmp_path / "ours.tfrecord")
    tfrecord.write_records(ours, payloads)
    got = [bytes(r.numpy()) for r in tf.data.TFRecordDataset(ours)]
    assert got == payloads


def test_crc32c_known_values():
    # RFC 3720 test vector: 32 zero bytes -> 0x8a9136aa.
    assert tfrecord.crc32c(b"\x00" * 32) == 0x8A9136AA
    assert tfrecord.crc32c(b"123456789") == 0xE3069283


def test_sharded_iterator_resume(tmp_path):
    paths = []
    for s in range(3):
        p = str(tmp_path / f"s{s}.tfrecord")
        tfrecord.write_records(
            p, [f"{s}-{i}".encode() for i in range(5)]
        )
        paths.append(p)
    it = tfrecord.ShardedRecordIterator(paths, seed=7)
    stream = iter(it)
    first = [next(stream) for _ in range(8)]
    state = it.get_state()

    it2 = tfrecord.ShardedRecordIterator(paths, seed=7)
    it2.set_state(state)
    rest = [next(iter(it2)) for _ in range(7)]

    it3 = tfrecord.ShardedRecordIterator(paths, seed=7)
    full = [next(iter(it3)) for _ in range(15)]
    assert first + rest == full


# --------------------------------------------------------------------------
# Example proto codec
# --------------------------------------------------------------------------


def test_example_roundtrip_self():
    feats = {
        "image/encoded": [b"\x00\x01jpegdata"],
        "image/class/label": [42],
        "bbox": [0.1, 0.2, 0.9, 0.8],
    }
    parsed = example_proto.parse_example(example_proto.build_example(feats))
    assert parsed["image/encoded"] == [b"\x00\x01jpegdata"]
    assert parsed["image/class/label"] == [42]
    np.testing.assert_allclose(parsed["bbox"], feats["bbox"], rtol=1e-6)


def test_example_matches_tf_oracle():
    tf = pytest.importorskip("tensorflow")
    ex = tf.train.Example(
        features=tf.train.Features(
            feature={
                "image/encoded": tf.train.Feature(
                    bytes_list=tf.train.BytesList(value=[b"rawbytes"])
                ),
                "image/class/label": tf.train.Feature(
                    int64_list=tf.train.Int64List(value=[7, -3])
                ),
                "w": tf.train.Feature(
                    float_list=tf.train.FloatList(value=[1.5, -2.25])
                ),
            }
        )
    )
    parsed = example_proto.parse_example(ex.SerializeToString())
    assert parsed["image/encoded"] == [b"rawbytes"]
    assert parsed["image/class/label"] == [7, -3]
    np.testing.assert_allclose(parsed["w"], [1.5, -2.25])

    # Reverse direction: TF parses what we build.
    ours = example_proto.build_example(
        {"label": [5], "name": [b"x"], "f": [0.5]}
    )
    parsed_tf = tf.io.parse_single_example(
        ours,
        {
            "label": tf.io.FixedLenFeature([], tf.int64),
            "name": tf.io.FixedLenFeature([], tf.string),
            "f": tf.io.FixedLenFeature([], tf.float32),
        },
    )
    assert int(parsed_tf["label"]) == 5
    assert bytes(parsed_tf["name"].numpy()) == b"x"
    assert float(parsed_tf["f"]) == 0.5


# --------------------------------------------------------------------------
# Augmentation
# --------------------------------------------------------------------------


def test_per_image_standardization_matches_tf():
    tf = pytest.importorskip("tensorflow")
    rng = np.random.RandomState(0)
    img = rng.rand(16, 16, 3).astype(np.float32)
    ours = augment.per_image_standardization(img)
    theirs = tf.image.per_image_standardization(img).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)
    assert abs(ours.mean()) < 1e-4
    # JAX batched variant agrees.
    jax_out = np.asarray(
        augment.jax_per_image_standardization(img[None])[0]
    )
    np.testing.assert_allclose(jax_out, ours, rtol=1e-4, atol=1e-5)


def test_cifar_train_preprocess_shapes_and_determinism():
    img = np.random.RandomState(1).rand(32, 32, 3).astype(np.float32)
    a = augment.preprocess_cifar_train(img, np.random.default_rng(3))
    b = augment.preprocess_cifar_train(img, np.random.default_rng(3))
    c = augment.preprocess_cifar_train(img, np.random.default_rng(4))
    assert a.shape == (32, 32, 3)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_distorted_bbox_crop_properties():
    rng = np.random.default_rng(0)
    areas = []
    for _ in range(50):
        top, left, h, w = augment.sample_distorted_bounding_box((480, 640), rng)
        assert 0 <= top <= 480 - h and 0 <= left <= 640 - w
        assert 1 <= h <= 480 and 1 <= w <= 640
        areas.append(h * w / (480 * 640))
        assert 0.6 <= (w / h) <= 1.45  # aspect within sampled range + rounding
    assert min(areas) < 0.4 and max(areas) > 0.5  # spans the area range


def test_imagenet_train_preprocess():
    img = (np.random.RandomState(2).rand(64, 80, 3) * 255).astype(np.uint8)
    out = augment.preprocess_imagenet_train(
        img, np.random.default_rng(1), size=32
    )
    assert out.shape == (32, 32, 3)
    assert out.min() >= -1.0 - 1e-6 and out.max() <= 1.0 + 1e-6


def test_imagenet_eval_preprocess_central_crop():
    img = (np.random.RandomState(2).rand(100, 100, 3) * 255).astype(np.uint8)
    out = augment.preprocess_imagenet_eval(img, size=24)
    assert out.shape == (24, 24, 3)


def test_jpeg_roundtrip_decode():
    yy, xx = np.mgrid[0:40, 0:40]
    img = np.stack([yy * 6, xx * 6, (yy + xx) * 3], axis=-1).astype(np.uint8)
    decoded = augment.decode_jpeg(augment.encode_jpeg(img, quality=95))
    assert decoded.shape == (40, 40, 3)
    assert np.abs(decoded.astype(int) - img.astype(int)).mean() < 8


def test_jax_random_crop_with_pad():
    import jax

    imgs = np.random.RandomState(0).rand(4, 8, 8, 3).astype(np.float32)
    out = augment.jax_random_crop_with_pad(imgs, jax.random.key(0), pad=2)
    assert out.shape == (4, 8, 8, 3)


# --------------------------------------------------------------------------
# Datasets
# --------------------------------------------------------------------------


def test_array_dataset_epochs_and_resume():
    x = np.arange(20, dtype=np.float32).reshape(20, 1)
    y = np.arange(20, dtype=np.int32)
    ds = datasets.ArrayDataset({"image": x, "label": y}, 4, seed=11)
    it = iter(ds)
    seen = [next(it) for _ in range(7)]  # crosses an epoch boundary
    state = ds.get_state()

    ds2 = datasets.ArrayDataset({"image": x, "label": y}, 4, seed=11)
    ds2.set_state(state)
    resumed = [next(iter(ds2)) for _ in range(3)]

    ds3 = datasets.ArrayDataset({"image": x, "label": y}, 4, seed=11)
    full = [next(iter(ds3)) for _ in range(10)]
    for a, b in zip(seen + resumed, full):
        np.testing.assert_array_equal(a["label"], b["label"])

    # Every epoch covers all samples exactly once.
    labels = np.concatenate([b["label"] for b in full[:5]])
    assert sorted(labels.tolist()) == list(range(20))


def test_mnist_cifar_shapes():
    b = next(iter(datasets.mnist_dataset(8)))
    assert b["image"].shape == (8, 28, 28, 1)
    b = next(iter(datasets.cifar10_dataset(8)))
    assert b["image"].shape == (8, 32, 32, 3)
    assert b["image"].dtype == np.float32
    # standardized: roughly zero mean per image
    assert abs(b["image"][0].mean()) < 0.1


def test_imagenet_tfrecord_dataset(tmp_path):
    paths = []
    rs = np.random.RandomState(0)
    for s in range(2):
        recs = []
        for i in range(6):
            img = (rs.rand(48, 56, 3) * 255).astype(np.uint8)
            recs.append(
                example_proto.build_example(
                    {
                        "image/encoded": [augment.encode_jpeg(img)],
                        "image/class/label": [1 + (s * 6 + i) % 10],
                    }
                )
            )
        p = str(tmp_path / f"train-{s:05d}")
        tfrecord.write_records(p, recs)
        paths.append(p)

    ds = datasets.ImageNetTFRecordDataset(
        paths, 4, train=True, image_size=32, label_offset=1
    )
    batch = next(iter(ds))
    assert batch["image"].shape == (4, 32, 32, 3)
    assert batch["label"].min() >= 0 and batch["label"].max() < 10

    state = ds.get_state()
    ds2 = datasets.ImageNetTFRecordDataset(
        paths, 4, train=True, image_size=32, label_offset=1
    )
    ds2.set_state(state)
    b2 = next(iter(ds2))
    b_cont = next(iter(ds))
    np.testing.assert_array_equal(b2["label"], b_cont["label"])


def test_ptb_dataset_windows_and_resume():
    tokens = np.arange(100, dtype=np.int32)
    ds = datasets.PTBDataset(tokens, batch_size=4, num_steps=5)
    it = iter(ds)
    b0 = next(it)
    assert b0["inputs"].shape == (4, 5)
    np.testing.assert_array_equal(b0["targets"], b0["inputs"] + 1)
    b1 = next(it)
    np.testing.assert_array_equal(b1["inputs"], b0["inputs"] + 5)

    state = ds.get_state()
    ds2 = datasets.PTBDataset(tokens, batch_size=4, num_steps=5)
    ds2.set_state(state)
    np.testing.assert_array_equal(next(iter(ds2))["inputs"], next(it)["inputs"])


def test_example_numpy_scalars_encode_correctly():
    feats = {
        "bbox": [np.float32(0.37), np.float32(0.9)],
        "label": [np.int64(3)],
    }
    parsed = example_proto.parse_example(example_proto.build_example(feats))
    np.testing.assert_allclose(parsed["bbox"], [0.37, 0.9], rtol=1e-6)
    assert parsed["label"] == [3]


def test_imagenet_eval_is_one_pass_with_partial_batch(tmp_path):
    recs = []
    for i in range(10):
        img = np.full((24, 24, 3), i * 20, np.uint8)
        recs.append(
            example_proto.build_example(
                {
                    "image/encoded": [augment.encode_jpeg(img)],
                    "image/class/label": [i],
                }
            )
        )
    p = str(tmp_path / "val-00000")
    tfrecord.write_records(p, recs)
    ds = datasets.ImageNetTFRecordDataset(
        [p], 4, train=False, image_size=16
    )
    batches = list(ds)
    assert [len(b["label"]) for b in batches] == [4, 4, 2]
    assert sorted(np.concatenate([b["label"] for b in batches])) == list(
        range(10)
    )


def test_sharded_iterator_native_true_requires_library(tmp_path):
    from distributed_tensorflow_models_tpu.data import native_loader

    p = str(tmp_path / "s")
    tfrecord.write_records(p, [b"x"])
    it = tfrecord.ShardedRecordIterator([p], native=True)
    if native_loader.available():
        assert next(iter(it)) == b"x"
    else:
        with pytest.raises(RuntimeError, match="native=True"):
            next(iter(it))


def test_synthetic_imagenet():
    ds = datasets.synthetic_imagenet_dataset(16, image_size=8)
    b = next(iter(ds))
    assert b["image"].shape == (16, 8, 8, 3)
    assert b["label"].max() < 1000


# --------------------------------------------------------------------------
# Host pipeline + device prefetch
# --------------------------------------------------------------------------


def test_host_pipeline_order_and_state():
    x = np.arange(24, dtype=np.float32).reshape(24, 1)
    y = np.arange(24, dtype=np.int32)
    ds = datasets.ArrayDataset({"image": x, "label": y}, 4, seed=2)
    pipe = pipeline.HostPipeline(ds, prefetch=2)
    got = [next(pipe) for _ in range(4)]
    state = pipe.get_state()
    pipe.stop()

    # Resume from the captured state reproduces the continuation.
    ds2 = datasets.ArrayDataset({"image": x, "label": y}, 4, seed=2)
    ds2.set_state(state)
    pipe2 = pipeline.HostPipeline(ds2, prefetch=2)
    b_resume = next(pipe2)
    pipe2.stop()

    ds3 = datasets.ArrayDataset({"image": x, "label": y}, 4, seed=2)
    ref = [next(iter(ds3)) for _ in range(5)]
    for a, b in zip(got, ref[:4]):
        np.testing.assert_array_equal(a["label"], b["label"])
    np.testing.assert_array_equal(b_resume["label"], ref[4]["label"])


def test_host_pipeline_propagates_errors():
    def bad_gen():
        yield {"x": np.zeros(1)}
        raise RuntimeError("producer exploded")

    pipe = pipeline.HostPipeline(bad_gen(), prefetch=1)
    next(pipe)
    with pytest.raises(RuntimeError, match="producer exploded"):
        next(pipe)
        next(pipe)


def test_host_pipeline_worker_count_invariance():
    """The pool contract: the emitted stream is bit-identical for any
    data_workers — ImageNet-synthetic (plain slicing) and CIFAR train
    (per-sample augmentation, rngs keyed by global sample position)."""
    builders = {
        "imagenet_synthetic": lambda: datasets.synthetic_imagenet_dataset(
            8, image_size=32, seed=7
        ),
        "cifar_augmented": lambda: datasets.cifar10_dataset(
            8, "train", seed=3
        ),
    }
    for name, fresh in builders.items():
        ref_it = iter(fresh())
        ref = [next(ref_it) for _ in range(10)]
        for workers in (1, 4):
            pipe = pipeline.HostPipeline(
                fresh(), prefetch=2, num_workers=workers
            )
            got = [next(pipe) for _ in range(10)]
            state = pipe.get_state()
            pipe.stop()
            for a, b in zip(got, ref):
                np.testing.assert_array_equal(
                    a["image"], b["image"], err_msg=f"{name} w={workers}"
                )
                np.testing.assert_array_equal(a["label"], b["label"])
            # State follows the last released batch regardless of pool
            # width: position 10, exactly where the serial path stands.
            assert state == {"epoch": 0, "batch_idx": 10}, (name, workers)


def test_host_pipeline_worker_pool_tfrecord_decode(tmp_path):
    """The decode-bound path through the pool: TFRecord shards → JPEG
    decode + distorted-bbox augment in parallel workers, stream and
    resume state identical to the serial iterator."""
    rs = np.random.RandomState(1)
    recs = []
    for i in range(12):
        img = (rs.rand(40, 40, 3) * 255).astype(np.uint8)
        recs.append(
            example_proto.build_example(
                {
                    "image/encoded": [augment.encode_jpeg(img)],
                    "image/class/label": [1 + i % 10],
                }
            )
        )
    p = str(tmp_path / "train-00000")
    tfrecord.write_records(p, recs)

    def fresh():
        return datasets.ImageNetTFRecordDataset(
            [p], 4, train=True, image_size=32, label_offset=1, seed=11
        )

    ref_it = iter(fresh())
    ref = [next(ref_it) for _ in range(5)]  # loops epochs past 12 records

    pipe = pipeline.HostPipeline(fresh(), prefetch=2, num_workers=2)
    got = [next(pipe) for _ in range(5)]
    state = pipe.get_state()
    pipe.stop()
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a["image"], b["image"])
        np.testing.assert_array_equal(a["label"], b["label"])

    # Resume from the pool-produced state = the serial continuation.
    ds2 = fresh()
    ds2.set_state(state)
    b_resume = next(iter(ds2))
    b_expect = next(ref_it)
    np.testing.assert_array_equal(b_resume["image"], b_expect["image"])
    np.testing.assert_array_equal(b_resume["label"], b_expect["label"])


class _ExplodingDataset:
    """Pool-protocol dataset whose assemble fails at one index; earlier
    items finish deliberately out of order (later index = faster)."""

    def __init__(self, boom_at=3):
        self._i = 0
        self._boom_at = boom_at

    def next_work(self):
        w = self._i
        self._i += 1
        return w

    def assemble(self, w):
        if w == self._boom_at:
            raise RuntimeError(f"boom at {w}")
        import time

        time.sleep(0.005 * (self._boom_at + 1 - min(w, self._boom_at)))
        return {"x": np.full((2,), w, np.float32)}

    def get_state(self):
        return {"i": self._i}


def test_host_pipeline_pool_error_surfaces_at_position():
    """Coordinator contract under the pool: every good batch before the
    failure index drains in order, THEN the error raises."""
    pipe = pipeline.HostPipeline(
        _ExplodingDataset(boom_at=3), prefetch=4, num_workers=4
    )
    got = []
    with pytest.raises(RuntimeError, match="boom at 3"):
        for _ in range(10):
            got.append(float(next(pipe)["x"][0]))
    assert got == [0.0, 1.0, 2.0]
    pipe.stop()  # error already consumed: must not re-raise


def test_host_pipeline_stop_raises_pending_error():
    """stop() must not silently drop a producer error the consumer never
    reached (the old pipeline.py:129-138 behavior)."""
    import time

    pipe = pipeline.HostPipeline(
        _ExplodingDataset(boom_at=2), prefetch=4, num_workers=2
    )
    assert float(next(pipe)["x"][0]) == 0.0
    for _ in range(200):  # wait for the failure to reach reassembly
        if pipe._error is not None:
            break
        time.sleep(0.01)
    with pytest.raises(RuntimeError, match="boom at 2"):
        pipe.stop()


def test_host_pipeline_stop_finds_error_still_in_flight():
    """A failure a worker produced but reassembly never walked past
    (blocked on a full consumer buffer) must still surface from stop()
    — swept from the in-flight queues, not silently dropped."""
    import time

    # prefetch=1 and no consumption: reassembly releases batch 0, blocks
    # on the full buffer; the failure at index 2 stays in flight.
    pipe = pipeline.HostPipeline(
        _ExplodingDataset(boom_at=2), prefetch=1, num_workers=2
    )
    for _ in range(200):  # wait until the failing assemble has run
        with pipe._results_q.mutex:
            in_q = any(
                isinstance(p, pipeline._Failure)
                for _, p, _ in list(pipe._results_q.queue)
            )
        in_pending = any(
            isinstance(p, pipeline._Failure)
            for p, _ in list(pipe._pending.values())
        )
        if in_q or in_pending or pipe._error is not None:
            break
        time.sleep(0.01)
    with pytest.raises(RuntimeError, match="boom at 2"):
        pipe.stop()


def test_host_pipeline_pool_winds_down_after_error():
    """After a mid-stream producer error the pool must stop feeding the
    results queue (an infinite dataset would otherwise free-run into
    unbounded memory while the consumer drains toward the error)."""
    import time

    pipe = pipeline.HostPipeline(
        _ExplodingDataset(boom_at=2), prefetch=4, num_workers=2
    )
    with pytest.raises(RuntimeError, match="boom at 2"):
        for _ in range(10):
            next(pipe)
    assert pipe._pool_stop.wait(timeout=2.0)
    for t in pipe._threads:
        t.join(timeout=2.0)
    assert not any(t.is_alive() for t in pipe._threads)
    assert pipe._results_q.qsize() <= 8  # bounded in-flight, not free-run
    pipe.stop()


def test_host_pipeline_pool_falls_back_without_protocol():
    """A plain iterable (no next_work/assemble) with num_workers>1 warns
    and degrades to the serial producer — never breaks."""

    def gen():
        for i in range(4):
            yield {"x": np.full((2,), i, np.float32)}

    pipe = pipeline.HostPipeline(gen(), prefetch=2, num_workers=4)
    got = [float(next(pipe)["x"][0]) for _ in range(4)]
    assert got == [0.0, 1.0, 2.0, 3.0]
    with pytest.raises(StopIteration):
        next(pipe)
    pipe.stop()


def test_host_queue_depth_reads_zero_when_drained():
    """The gauge is sampled on the consumer side too: after the stream is
    fully drained it must read 0, not the last produced depth."""
    from distributed_tensorflow_models_tpu import telemetry

    def gen():
        for i in range(3):
            yield {"x": np.full((2,), i, np.float32)}

    reg = telemetry.MetricsRegistry()
    pipe = pipeline.HostPipeline(gen(), prefetch=4, registry=reg)
    for _ in range(3):
        next(pipe)
    with pytest.raises(StopIteration):
        next(pipe)
    assert reg.gauge(telemetry.HOST_QUEUE_DEPTH).value == 0.0
    pipe.stop()


def test_device_prefetcher(mesh8):
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    y = np.arange(8, dtype=np.int32)
    ds = datasets.ArrayDataset({"image": x, "label": y}, 8, seed=0)
    pre = pipeline.DevicePrefetcher(ds, mesh8, depth=2)
    batch = next(pre)
    import jax

    assert isinstance(batch["image"], jax.Array)
    assert batch["image"].shape == (8, 8)
    # Sharded over the data axis of the mesh.
    assert not batch["image"].sharding.is_fully_replicated


# --------------------------------------------------------------------------
# Recycled batch buffers (HostPipeline.release -> ArrayDataset.recycle)
# --------------------------------------------------------------------------


def _recycling_dataset(transform, seed=11):
    """48 rows in batches of 8: six batches an epoch."""
    rng = np.random.RandomState(0)
    x = rng.rand(48, 6, 6, 3).astype(np.float32)
    y = np.arange(48, dtype=np.int32)
    return datasets.ArrayDataset(
        {"image": x, "label": y},
        8,
        seed=seed,
        transform=augment.preprocess_cifar_train if transform else None,
    )


def _assert_batches_equal(got, want, msg=""):
    assert got.keys() == want.keys()
    for k in want:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_array_equal(got[k], want[k], err_msg=f"{msg} {k}")


def _address(batch):
    return batch["image"].ctypes.data


@pytest.mark.parametrize(
    "transform", [False, True], ids=["gather", "transform"]
)
@pytest.mark.parametrize("workers", [1, 3])
def test_released_stream_equals_unreleased_stream(workers, transform):
    """Releasing every batch changes where the bytes land and nothing
    else: across two epoch boundaries and a set_state resume the stream
    is byte-identical to the dataset's plain iteration, which never
    recycles — and the recycling did engage."""
    from distributed_tensorflow_models_tpu import telemetry

    ref_it = iter(_recycling_dataset(transform))
    reg = telemetry.MetricsRegistry()
    pipe = pipeline.HostPipeline(
        _recycling_dataset(transform), prefetch=2, num_workers=workers,
        registry=reg,
    )
    for i in range(14):
        batch = next(pipe)
        _assert_batches_equal(batch, next(ref_it), f"w={workers} batch {i}")
        pipe.release(batch)
    state = pipe.get_state()
    pipe.stop()
    assert state == {"epoch": 2, "batch_idx": 2}
    assert reg.counter(telemetry.BUFFER_REUSED).value > 0

    resumed = _recycling_dataset(transform)
    resumed.set_state(state)
    pipe = pipeline.HostPipeline(resumed, prefetch=2, num_workers=workers)
    for i in range(14, 20):
        batch = next(pipe)
        _assert_batches_equal(batch, next(ref_it), f"resumed batch {i}")
        pipe.release(batch)
    pipe.stop()


def test_released_buffer_is_reused_and_counted():
    """One release feeds exactly one later batch: it lands at the same
    address, and the two counters say one recycled, the rest allocated."""
    from distributed_tensorflow_models_tpu import telemetry

    reg = telemetry.MetricsRegistry()
    pipe = pipeline.HostPipeline(
        _recycling_dataset(False), prefetch=1, registry=reg
    )
    first = next(pipe)
    address = _address(first)
    pipe.release(first)
    del first
    later = [next(pipe) for _ in range(6)]
    pipe.stop()
    assert [_address(b) for b in later].count(address) == 1
    snap = reg.snapshot()
    assert snap[telemetry.BUFFER_REUSED] == 1
    assert snap[telemetry.BUFFER_FRESH] >= 6
    assert (
        snap[telemetry.BUFFER_REUSED] + snap[telemetry.BUFFER_FRESH]
        == snap[f"{telemetry.ASSEMBLE}/count"]
    )


@pytest.mark.parametrize("workers", [1, 3])
def test_unreleased_batch_is_never_rewritten(workers):
    """A consumer that keeps a batch and releases others: the kept
    arrays stay what they were while later batches are produced, and no
    later batch lands in their memory."""
    ref_it = iter(_recycling_dataset(True))
    pipe = pipeline.HostPipeline(
        _recycling_dataset(True), prefetch=2, num_workers=workers
    )
    kept = []
    later_addresses = set()
    for i in range(24):
        batch = next(pipe)
        want = next(ref_it)
        later_addresses.add(_address(batch))
        if i % 3 == 0:
            kept.append((batch, want))
        else:
            pipe.release(batch)
        for held, expect in kept:
            _assert_batches_equal(held, expect, f"kept, at batch {i}")
    pipe.stop()
    # 8 kept arrays, all distinct memory, none handed out twice.
    assert len({_address(b) for b, _ in kept}) == len(kept) == 8
    assert len(later_addresses) < 24  # and the released ones were reused


@pytest.mark.parametrize("workers", [1, 3])
def test_retention_is_bounded_by_the_pipeline_depths(workers):
    """A consumer that hoards 30 batches and then releases them all
    leaves at most prefetch + num_workers + downstream + 1 buffers to
    reuse; the rest go to the garbage collector."""
    prefetch, downstream = 2, 2
    bound = prefetch + workers + downstream + 1
    pipe = pipeline.HostPipeline(
        _recycling_dataset(False), prefetch=prefetch, num_workers=workers
    )
    hoard = [next(pipe) for _ in range(30)]
    for batch in hoard:
        pipe.release(batch, downstream=downstream)
        pipe.release(batch, downstream=downstream)  # twice: kept once
    # ``hoard`` stays alive, so malloc cannot hand an address out again.
    released = {_address(b) for b in hoard}
    assert len(released) == 30
    again = [next(pipe) for _ in range(30)]
    pipe.stop()
    reused = [_address(b) for b in again if _address(b) in released]
    assert 1 <= len(reused) <= bound
    assert len(set(reused)) == len(reused)


def test_recycle_ignores_what_assemble_did_not_make():
    ds = _recycling_dataset(False)
    made = ds.assemble(ds.next_work())
    foreign = {
        "image": np.zeros((4, 6, 6, 3), np.float32),  # another shape
        "label": made["label"][:],  # a view
        "extra": np.zeros(8, np.int32),  # another key
    }
    ds.recycle(foreign, limit=4)
    nxt = ds.assemble(ds.next_work())
    assert not ds.last_assemble_reused()
    assert not np.shares_memory(nxt["label"], made["label"])
    ds.recycle(made, limit=4)
    ds.assemble(ds.next_work())
    assert ds.last_assemble_reused()


def test_recycling_survives_a_thread_stress():
    """Eight threads assemble, hold, check and give back batches of one
    dataset at a short switch interval: every batch still holds its own
    work item's bytes when its holder looks (two holders of one buffer
    would not), and the free lists stay within their limit."""
    import sys
    import threading
    import time

    ds = _recycling_dataset(True)
    ref = _recycling_dataset(True)
    works = [ds.next_work() for _ in range(240)]
    expected = {w: ref.assemble(w) for w in works}
    wrong = []

    def hold_and_check(mine):
        for w in mine:
            batch = ds.assemble(w)
            time.sleep(0)  # let the others write
            if not all(
                np.array_equal(batch[k], expected[w][k]) for k in batch
            ):
                wrong.append(w)
            ds.recycle(batch, limit=4)

    threads = [
        threading.Thread(target=hold_and_check, args=(works[i::8],))
        for i in range(8)
    ]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not wrong
    assert ds._free and all(0 < len(v) <= 4 for v in ds._free.values())


def test_device_prefetcher_never_releases_on_a_cpu_mesh(mesh8):
    """The aliasing case: a CPU device array may be a zero-copy view of
    the numpy batch, so on a CPU mesh the host batch is never given
    back.  Device batches held alive across six later pulls still equal
    the reference stream, and nothing was recycled."""
    from distributed_tensorflow_models_tpu import telemetry

    ref_it = iter(_recycling_dataset(False))
    reg = telemetry.MetricsRegistry()
    host = pipeline.HostPipeline(
        _recycling_dataset(False), prefetch=2, registry=reg
    )
    pre = pipeline.DevicePrefetcher(host, mesh8, depth=2, registry=reg)
    held = []
    for i in range(14):
        held.append((next(pre), next(ref_it)))
        for placed, want in held[-7:]:
            for k in want:
                np.testing.assert_array_equal(
                    np.asarray(placed[k]), want[k], err_msg=f"at pull {i}"
                )
    host.stop()
    assert reg.counter(telemetry.BUFFER_REUSED).value == 0


class _SlowTransfer:
    """A placed leaf whose transfer out of the host array ends only when
    the test says so: until then it aliases the host memory (what an
    asynchronous DMA reads), from then on it holds its own copy."""

    def __init__(self, host):
        self._host = host
        self._copy = None

    def finish(self):
        if self._copy is None:
            self._copy = self._host.copy()
            self._host = None

    def is_ready(self):
        return self._copy is not None

    def is_deleted(self):
        return False

    def value(self):
        return self._host if self._copy is None else self._copy


def test_device_prefetcher_releases_only_after_the_transfer_is_over(
    monkeypatch,
):
    """On a backend that copies out of the host batch asynchronously,
    the prefetcher gives a host batch back only once every array placed
    from it is ready: while transfers lag, nothing is recycled and every
    pending transfer still reads its own batch; once they end, later
    batches land in recycled buffers; and a transfer that never ends
    costs its buffer, not the loop's time.  (The accelerator is stood in
    for by a mesh that reports its platform and a placement whose
    transfers the test ends by hand.)"""
    import types

    import jax

    from distributed_tensorflow_models_tpu import telemetry
    from distributed_tensorflow_models_tpu.core import sharding

    monkeypatch.setattr(
        sharding,
        "shard_batch",
        lambda mesh, batch, seq_dim=None: jax.tree.map(_SlowTransfer, batch),
    )
    accelerator = types.SimpleNamespace(
        devices=np.array([types.SimpleNamespace(platform="tpu")])
    )
    ref_it = iter(_recycling_dataset(False))
    reg = telemetry.MetricsRegistry()
    host = pipeline.HostPipeline(
        _recycling_dataset(False), prefetch=2, registry=reg
    )
    pre = pipeline.DevicePrefetcher(host, accelerator, depth=2, registry=reg)
    held = []

    def pull_and_check(n):
        for _ in range(n):
            held.append((next(pre), next(ref_it)))
            for placed, want in held:
                for k in want:
                    np.testing.assert_array_equal(placed[k].value(), want[k])

    # Ten pulls with every transfer lagging: four wait (depth + the
    # upstream's prefetch), the older ones are dropped unreleased, and
    # no batch is rewritten under its transfer.
    pull_and_check(10)
    assert reg.counter(telemetry.BUFFER_REUSED).value == 0
    assert len(pre._unreleased) == 4
    # The transfers end: the waiting batches go back at the next pulls
    # and the producer writes into them.
    for placed, _ in held:
        jax.tree.map(_SlowTransfer.finish, placed)
    pull_and_check(2)
    for placed, _ in held[-2:]:
        jax.tree.map(_SlowTransfer.finish, placed)
    pull_and_check(8)
    host.stop()
    assert reg.counter(telemetry.BUFFER_REUSED).value >= 4


def test_worker_pool_is_bounded_by_its_consumer():
    """Three workers and a slow consumer: the items in flight
    (dispatched, not yet handed to the consumer buffer) never exceed
    num_workers + prefetch, so the workers stop when the consumer does
    (unbounded before: 145,068 batches assembled in a 6-step drive)."""
    import threading
    import time

    workers, prefetch = 3, 2
    ds = _recycling_dataset(False)
    assembled = 0
    lock = threading.Lock()
    inner = ds.assemble

    def counting_assemble(work):
        nonlocal assembled
        batch = inner(work)
        with lock:
            assembled += 1
        return batch

    ds.assemble = counting_assemble
    pipe = pipeline.HostPipeline(ds, prefetch=prefetch, num_workers=workers)
    for consumed in range(1, 7):
        next(pipe)
        time.sleep(0.05)
        # Read what was assembled first, what was delivered after: the
        # difference can only under-read what is in flight.
        n = assembled
        in_flight = n - consumed - pipe._buffer.qsize()
        assert in_flight <= workers + prefetch, (consumed, n)
    time.sleep(0.3)
    assert assembled <= 6 + prefetch + workers + prefetch
    pipe.stop()


# --------------------------------------------------------------------------
# Multi-host sharding (SURVEY.md §3.4: per-worker input streams)
# --------------------------------------------------------------------------


def test_array_dataset_process_shards_concat_to_global_batch():
    """Process-order concatenation of per-process slices must reproduce the
    single-process global batch exactly — including deterministic
    augmentation (rngs keyed by global sample position)."""
    full = datasets.cifar10_dataset(8, "train", seed=3)
    parts = [
        datasets.cifar10_dataset(
            8, "train", seed=3, process_index=p, process_count=2
        )
        for p in range(2)
    ]
    fit, pits = iter(full), [iter(p) for p in parts]
    for _ in range(3):  # spans an epoch boundary reshuffle at 8192/8
        fb = next(fit)
        pbs = [next(it) for it in pits]
        assert all(pb["image"].shape[0] == 4 for pb in pbs)
        np.testing.assert_array_equal(
            fb["image"], np.concatenate([pb["image"] for pb in pbs])
        )
        np.testing.assert_array_equal(
            fb["label"], np.concatenate([pb["label"] for pb in pbs])
        )


def test_array_dataset_rejects_indivisible_process_count():
    with pytest.raises(ValueError):
        datasets.mnist_dataset(8, process_index=0, process_count=3)


def test_ptb_dataset_process_shards_are_row_blocks():
    tokens = np.arange(100, dtype=np.int32)
    full = datasets.PTBDataset(tokens, batch_size=4, num_steps=5)
    parts = [
        datasets.PTBDataset(
            tokens,
            batch_size=4,
            num_steps=5,
            process_index=p,
            process_count=2,
        )
        for p in range(2)
    ]
    fb = next(iter(full))
    pbs = [next(iter(p)) for p in parts]
    np.testing.assert_array_equal(
        fb["inputs"], np.concatenate([pb["inputs"] for pb in pbs])
    )
    np.testing.assert_array_equal(
        fb["targets"], np.concatenate([pb["targets"] for pb in pbs])
    )


def _write_imagenet_shards(tmp_path, n_shards, per_shard, prefix="train"):
    paths = []
    for s in range(n_shards):
        recs = []
        for i in range(per_shard):
            img = np.full((24, 24, 3), (s * per_shard + i) * 5, np.uint8)
            recs.append(
                example_proto.build_example(
                    {
                        "image/encoded": [augment.encode_jpeg(img)],
                        "image/class/label": [s * per_shard + i],
                    }
                )
            )
        p = str(tmp_path / f"{prefix}-{s:05d}")
        tfrecord.write_records(p, recs)
        paths.append(p)
    return paths


def test_imagenet_train_file_sharding_is_disjoint(tmp_path):
    paths = _write_imagenet_shards(tmp_path, n_shards=2, per_shard=6)
    parts = [
        datasets.ImageNetTFRecordDataset(
            paths,
            4,
            train=True,
            image_size=16,
            process_index=p,
            process_count=2,
        )
        for p in range(2)
    ]
    seen = []
    for part in parts:
        it = iter(part)
        labels = np.concatenate([next(it)["label"] for _ in range(3)])
        assert len(labels) == 6  # local batch 2, file of 6 records
        seen.append(set(labels.tolist()))
    # Each process consumed exactly one whole shard file; no overlap.
    assert seen[0] | seen[1] == set(range(12))
    assert not (seen[0] & seen[1])


def test_imagenet_train_replicated_fallback_matches_global(tmp_path):
    """With fewer shard files than processes the dataset falls back to
    replicated reads + row slicing, which must reproduce the single-process
    batches exactly (augment rng keyed by global record count)."""
    paths = _write_imagenet_shards(tmp_path, n_shards=1, per_shard=8)
    full = datasets.ImageNetTFRecordDataset(
        paths, 4, train=True, image_size=16, seed=7
    )
    parts = [
        datasets.ImageNetTFRecordDataset(
            paths,
            4,
            train=True,
            image_size=16,
            seed=7,
            process_index=p,
            process_count=2,
        )
        for p in range(2)
    ]
    fb = next(iter(full))
    pbs = [next(iter(p)) for p in parts]
    np.testing.assert_array_equal(
        fb["image"], np.concatenate([pb["image"] for pb in pbs])
    )
    np.testing.assert_array_equal(
        fb["label"], np.concatenate([pb["label"] for pb in pbs])
    )


def test_imagenet_eval_multiprocess_pads_final_batch(tmp_path):
    paths = _write_imagenet_shards(
        tmp_path, n_shards=1, per_shard=10, prefix="val"
    )
    parts = [
        datasets.ImageNetTFRecordDataset(
            paths,
            4,
            train=False,
            image_size=16,
            process_index=p,
            process_count=2,
        )
        for p in range(2)
    ]
    batches = [list(p) for p in parts]
    # 10 records, global batch 4 -> 3 global batches, last padded.
    assert [len(bs) for bs in batches] == [3, 3]
    for bs in batches:
        assert all(b["label"].shape == (2,) for b in bs)
    labels = np.stack(
        [np.concatenate([b["label"] for b in bs]) for bs in batches]
    )
    # Row blocks interleave back into the global record order.
    merged = np.concatenate(
        [
            np.stack([labels[0, i * 2 : i * 2 + 2],
                      labels[1, i * 2 : i * 2 + 2]]).reshape(-1)
            for i in range(3)
        ]
    )
    np.testing.assert_array_equal(
        merged, np.array([0, 1, 2, 3, 4, 5, 6, 7, 8, 9, -1, -1])
    )
