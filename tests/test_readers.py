"""In-graph reader pipeline (reference operators/reader/*,
python/paddle/fluid/layers/io.py:281-490): reader variables created by
startup ops, `read` op feeding the device program, double-buffer async
prefetch, EOF + reset semantics."""
import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import core, layers
from paddle_tpu.fluid.framework import Program, program_guard
from paddle_tpu.fluid.recordio_writer import convert_reader_to_recordio_file

N_SAMPLES = 20


def _write_file(tmp_path, n=N_SAMPLES):
    path = str(tmp_path / "data.recordio")

    def reader():
        rng = np.random.RandomState(7)
        for i in range(n):
            x = rng.rand(4).astype(np.float32)
            y = np.array([i % 2], dtype=np.int64)
            yield (x, y)

    count = convert_reader_to_recordio_file(path, reader)
    assert count == n
    return path


def _build(path, batch_size=4, use_double_buffer=True, drop_last=True):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        reader = layers.open_recordio_file(
            path, shapes=[[4], [1]], dtypes=["float32", "int64"]
        )
        reader = layers.batch(reader, batch_size=batch_size,
                              drop_last=drop_last)
        if use_double_buffer:
            reader = layers.double_buffer(reader)
        x, y = layers.read_file(reader)
        pred = layers.fc(input=x, size=2, act="softmax")
        cost = layers.mean(layers.cross_entropy(input=pred, label=y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)
    return main, startup, reader, cost


def test_recordio_reader_trains_and_eofs(tmp_path):
    path = _write_file(tmp_path)
    main, startup, reader, cost = _build(path)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        losses = []
        with pytest.raises(core.EOFException):
            while True:
                (l,) = exe.run(main, fetch_list=[cost])
                losses.append(float(l.ravel()[0]))
        assert len(losses) == N_SAMPLES // 4  # drop_last, bs=4
        assert all(np.isfinite(losses))
        # reset and run a second epoch without re-initializing params
        layers.reset_reader(reader, scope)
        (l2,) = exe.run(main, fetch_list=[cost])
        assert np.isfinite(float(l2.ravel()[0]))


def test_rerunning_startup_resets_pipeline(tmp_path):
    path = _write_file(tmp_path)
    main, startup, reader, cost = _build(path, use_double_buffer=False)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(2):
            exe.run(main, fetch_list=[cost])
        exe.run(startup)  # reference ReInit semantics
        n = 0
        with pytest.raises(core.EOFException):
            while True:
                exe.run(main, fetch_list=[cost])
                n += 1
        assert n == N_SAMPLES // 4  # full epoch again after reset


def test_shuffle_and_multi_pass(tmp_path):
    path = _write_file(tmp_path)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        reader = layers.open_recordio_file(
            path, shapes=[[4], [1]], dtypes=["float32", "int64"]
        )
        reader = layers.multi_pass(reader, pass_num=3)
        reader = layers.shuffle(reader, buffer_size=8, seed=5)
        reader = layers.batch(reader, batch_size=5, drop_last=True)
        x, y = layers.read_file(reader)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        n = 0
        with pytest.raises(core.EOFException):
            while True:
                exe.run(main, fetch_list=[x, y])
                n += 1
        assert n == 3 * N_SAMPLES // 5


def test_open_files_multi_shard(tmp_path):
    from paddle_tpu.fluid.recordio_writer import (
        convert_reader_to_recordio_files,
    )

    def reader():
        for i in range(12):
            yield (np.full((3,), i, dtype=np.float32),)

    files = convert_reader_to_recordio_files(
        str(tmp_path / "shard"), batch_per_file=5, reader_creator=reader
    )
    assert len(files) == 3
    main, startup = Program(), Program()
    with program_guard(main, startup):
        r = layers.open_files(files, shapes=[[3]], dtypes=["float32"])
        r = layers.batch(r, batch_size=3, drop_last=False)
        (x,) = layers.read_file(r)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        seen = []
        with pytest.raises(core.EOFException):
            while True:
                (v,) = exe.run(main, fetch_list=[x])
                seen.extend(v[:, 0].tolist())
        assert sorted(seen) == sorted(float(i) for i in range(12))


def test_double_buffer_overlaps_decode(tmp_path):
    """The async contract: with a slow decoder, double_buffer hides decode
    time behind consumer time (round-2 review item 2's 'done' bar, scaled to a
    unit test)."""
    from paddle_tpu.fluid.readers import DoubleBufferReader, HostReader

    DECODE_S = 0.05

    class Slow(HostReader):
        def __init__(self):
            self.i = 0

        def read_next(self):
            if self.i >= 8:
                raise StopIteration
            time.sleep(DECODE_S)  # pretend jpeg decode
            self.i += 1
            return (np.full((2,), self.i, dtype=np.float32),)

        def reset(self):
            self.i = 0

    def consume(reader):
        t0 = time.perf_counter()
        n = 0
        while True:
            try:
                reader.read_next()
            except StopIteration:
                break
            n += 1
            time.sleep(DECODE_S)  # pretend device step
        assert n == 8
        return time.perf_counter() - t0

    serial = consume(Slow())
    db = DoubleBufferReader(Slow(), capacity=2, device_put=False)
    try:
        overlapped = consume(db)
    finally:
        db.close()
    # serial ~= 8*(decode+step); overlapped ~= 8*step (+1 decode). Require
    # a >=25% cut to stay robust on loaded CI
    assert overlapped < serial * 0.75, (overlapped, serial)


def test_double_buffer_reset_and_error_propagation(tmp_path):
    from paddle_tpu.fluid.readers import DoubleBufferReader, HostReader

    class Boom(HostReader):
        def __init__(self):
            self.n = 0

        def read_next(self):
            self.n += 1
            if self.n == 3:
                raise IOError("decode failed")
            return (np.zeros(1, dtype=np.float32),)

        def reset(self):
            self.n = 0

    db = DoubleBufferReader(Boom(), capacity=1, device_put=False)
    try:
        db.read_next()
        db.read_next()
        with pytest.raises(IOError, match="decode failed"):
            # the worker died on sample 3; the error surfaces here
            db.read_next()
    finally:
        db.close()

    path = _write_file(tmp_path, n=8)
    main, startup, reader, cost = _build(path, batch_size=4)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(2):
            exe.run(main, fetch_list=[cost])
        layers.reset_reader(reader, scope)
        n = 0
        with pytest.raises(core.EOFException):
            while True:
                exe.run(main, fetch_list=[cost])
                n += 1
        assert n == 2


def test_double_buffer_transfer_error_stops_both_stages():
    """A failure in the TRANSFER stage must surface at read_next() AND
    stop the decode stage — otherwise the decoder keeps draining the
    inner reader and busy-polls a full queue forever after the caller
    abandons the reader (two-stage pipeline regression guard)."""
    from paddle_tpu.fluid.readers import DoubleBufferReader, HostReader

    class Counting(HostReader):
        def __init__(self):
            self.n = 0

        def read_next(self):
            self.n += 1
            # object arrays make jnp.asarray raise in the transfer stage
            return (np.array([object()]),)

        def reset(self):
            self.n = 0

    src = Counting()
    db = DoubleBufferReader(src, capacity=2, device_put=True)
    try:
        with pytest.raises(Exception):
            db.read_next()
        # the decode stage observed the stop flag: it reads at most the
        # in-flight capacity worth of extra samples, then halts
        for _ in range(50):
            if not db._thread or not db._thread.is_alive():
                break
            time.sleep(0.05)
        else:
            pytest.fail("decode thread still alive after transfer error")
        reads_after_error = src.n
        time.sleep(0.2)
        assert src.n == reads_after_error  # no further inner reads
    finally:
        db.close()


def test_reader_program_desc_roundtrip(tmp_path):
    """Reader slots survive Program serialization (the reference's
    VarType.ReaderDesc round-trip)."""
    path = _write_file(tmp_path)
    main, startup, reader, cost = _build(path, use_double_buffer=True)
    from paddle_tpu.fluid.framework import Program as P

    clone = P.parse_from_bytes(startup.to_bytes())
    svar = [v for v in clone.global_block().vars.values()
            if v.desc.type == core.VarType.READER.value]
    assert svar and all(v.desc.reader_slots for v in svar)
    clone_main = P.parse_from_bytes(main.to_bytes())
    assert clone_main.to_bytes() == main.to_bytes()


def test_batch_reader_pads_ragged_slots(tmp_path):
    """lod_level>0 slots batch into (padded, lengths) — the padded+@LEN
    ragged representation the read op feeds downstream."""
    path = str(tmp_path / "seq.recordio")

    def reader():
        rng = np.random.RandomState(11)
        for i in range(9):
            seq_len = 2 + i % 4
            yield (rng.rand(seq_len, 3).astype(np.float32),
                   np.array([i % 2], dtype=np.int64))

    convert_reader_to_recordio_file(path, reader)
    main, startup = Program(), Program()
    with program_guard(main, startup):
        r = layers.open_recordio_file(
            path, shapes=[[-1, 3], [1]], dtypes=["float32", "int64"],
            lod_levels=[1, 0],
        )
        r = layers.batch(r, batch_size=3, drop_last=True)
        x, y = layers.read_file(r)
        assert main.current_block().has_var(x.name + "@LEN")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        xs, lens = exe.run(main, fetch_list=[x, x.name + "@LEN"])
        assert xs.ndim == 3 and xs.shape[0] == 3 and xs.shape[2] == 3
        assert lens.tolist() == [2, 3, 4]
        assert xs.shape[1] == max(lens)
        # padding is zero past each row's length
        assert np.all(xs[0, 2:] == 0)


def test_double_buffer_dead_worker_reraises():
    """After the worker dies on an error, further reads re-raise instead of
    blocking forever on an empty queue."""
    from paddle_tpu.fluid.readers import DoubleBufferReader, HostReader

    class Boom(HostReader):
        def read_next(self):
            raise IOError("decode failed")

        def reset(self):
            pass

    db = DoubleBufferReader(Boom(), capacity=1, device_put=False)
    try:
        for _ in range(3):  # every attempt fails fast, none hangs
            with pytest.raises(IOError, match="decode failed"):
                db.read_next()
    finally:
        db.close()


def test_uint8_on_the_wire_with_in_graph_decode(tmp_path):
    """The transfer-bound-link pipeline shape (input_pipeline_bench):
    uint8 images stay uint8 through batching, the double-buffer stages,
    and the device transfer; the f32 decode + 1/255 scale runs IN-GRAPH.
    Trains end to end and the decoded values match the stored bytes."""
    path = str(tmp_path / "u8.recordio")
    rng = np.random.RandomState(3)
    imgs = rng.randint(0, 256, size=(12, 6), dtype=np.uint8).astype(np.uint8)

    def gen():
        for i in range(12):
            yield (imgs[i], np.array([i % 2], dtype=np.int64))

    convert_reader_to_recordio_file(path, gen)

    main, startup = Program(), Program()
    with program_guard(main, startup):
        reader = layers.open_recordio_file(
            path, shapes=[[6], [1]], dtypes=["uint8", "int64"])
        reader = layers.batch(reader, batch_size=4, drop_last=True)
        reader = layers.double_buffer(reader, capacity=2)
        raw, label = layers.read_file(reader)
        img = layers.scale(layers.cast(raw, "float32"), 1.0 / 255.0)
        pred = layers.fc(input=img, size=2, act="softmax")
        cost = layers.mean(layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(cost)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        # fetch the decoded batch alongside the loss: values must equal
        # bytes/255 for the first (in-order) batch
        out = exe.run(main, fetch_list=[img, cost])
        np.testing.assert_allclose(
            np.asarray(out[0]), imgs[:4].astype(np.float32) / 255.0,
            rtol=1e-6)
        assert np.isfinite(np.asarray(out[1])).all()
        n = 1
        with pytest.raises(core.EOFException):
            while True:
                exe.run(main, fetch_list=[cost])
                n += 1
        assert n == 3  # 12 samples / bs 4
