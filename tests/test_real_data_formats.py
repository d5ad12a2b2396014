"""Real wire-format evidence for the data plane (round-4 review item 2): the
dataset parsers read byte-GENUINE fixtures in the original formats —
gzipped IDX (magic 0x803/0x801), cifar python-pickle tarballs, an aclImdb
tar fragment, a wmt sentence-pair tgz — committed under tests/fixtures/
(generated by tools/make_fixtures.py). Expected values are derived
INDEPENDENTLY from the fixtures' generating formulas, so any byte-layout
mistake in a parser (endianness, header size, member selection,
tokenization) fails these tests.

Reference formats: python/paddle/dataset/mnist.py:49-52 (IDX),
cifar.py:47 (pickle tar), imdb.py:36 (aclImdb tokenize), wmt14.py:52/78
(dict + tab-separated pairs)."""
import os

import numpy as np
import pytest

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


@pytest.fixture()
def real_data(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_DATA", FIXTURES)


def test_mnist_idx_bytes(real_data):
    import paddle_tpu.dataset.common as common
    import paddle_tpu.dataset.mnist as mnist

    assert common.data_source("mnist") == "real"
    rows = list(mnist.train()())
    assert len(rows) == 32
    img, label = rows[5]
    assert img.shape == (784,) and img.dtype == np.float32
    assert label == 5
    # pixel (i, r, c) = (i*7 + r*3 + c) % 256, scaled to [-1, 1] — a
    # wrong header size or row order scrambles this
    i, r, c = 5, 3, 11
    expected = ((i * 7 + r * 3 + c) % 256) / 127.5 - 1.0
    np.testing.assert_allclose(img[r * 28 + c], expected, rtol=1e-6)
    # test split has its own sizes
    assert len(list(mnist.test()())) == 16


def test_mnist_rejects_wrong_magic(real_data, tmp_path, monkeypatch):
    """A malformed IDX header must not parse quietly into garbage: the
    count field is part of the contract."""
    import gzip
    import struct

    import paddle_tpu.dataset.mnist as mnist

    d = tmp_path / "mnist"
    d.mkdir()
    # little-endian header (wrong): count decodes astronomically large
    payload = struct.pack("<IIII", 0x803, 4, 28, 28) + b"\x00" * (4 * 784)
    for name in ("train-images-idx3-ubyte.gz", "t10k-images-idx3-ubyte.gz"):
        with gzip.open(d / name, "wb") as f:
            f.write(payload)
    lbl = struct.pack(">II", 0x801, 4) + b"\x00\x01\x02\x03"
    for name in ("train-labels-idx1-ubyte.gz", "t10k-labels-idx1-ubyte.gz"):
        with gzip.open(d / name, "wb") as f:
            f.write(lbl)
    monkeypatch.setenv("PADDLE_TPU_DATA", str(tmp_path))
    with pytest.raises(Exception):
        list(mnist.train()())


def test_cifar_pickle_tar_bytes(real_data):
    import pickle
    import tarfile

    import paddle_tpu.dataset.cifar as cifar
    import paddle_tpu.dataset.common as common

    assert common.data_source("cifar") == "real"
    rows = list(cifar.train10()())
    assert len(rows) == 16  # 2 batches x 8
    img, label = rows[0]
    assert img.shape == (3072,) and img.dtype == np.float32
    assert 0 <= label <= 9
    # cross-check against an independent unpickle of the fixture
    tar = os.path.join(FIXTURES, "cifar", "cifar-10-python.tar.gz")
    with tarfile.open(tar) as f:
        batch = pickle.load(
            f.extractfile("cifar-10-batches-py/data_batch_1"),
            encoding="bytes")
    np.testing.assert_allclose(
        img, batch[b"data"][0].astype(np.float32) / 255.0, rtol=1e-6)
    assert label == batch[b"labels"][0]
    assert len(list(cifar.test10()())) == 8

    # cifar-100 uses fine_labels and single train/test members; its
    # provenance is queried with the explicit file name
    assert common.data_source("cifar", "cifar-100-python.tar.gz") == "real"
    rows100 = list(cifar.train100()())
    assert len(rows100) == 12
    assert all(0 <= l <= 99 for _, l in rows100)
    assert len(list(cifar.test100()())) == 6


def test_imdb_aclimdb_tokenization(real_data):
    import paddle_tpu.dataset.common as common
    import paddle_tpu.dataset.imdb as imdb

    assert common.data_source("imdb") == "real"
    wd = imdb.word_dict()
    # frequency order from the fixture corpus: 'wonderful' x3, 'a' x3,
    # 'terrible' x3 ... '<unk>' appended last. Spot-check invariants
    # rather than the full order: every fixture token is present,
    # punctuation stripped, case folded.
    for w in ("wonderful", "great", "terrible", "awful", "film", "a"):
        assert w in wd, sorted(wd)
    assert "wonderful," not in wd and "WONDERFUL" not in wd
    assert wd["<unk>"] == len(wd) - 1
    rows = list(imdb.train(wd)())
    # 2 pos (label 0) then 2 neg (label 1) — the reference's ordering
    assert [l for _, l in rows] == [0, 0, 1, 1]
    ids, _ = rows[0]
    # "A wonderful, WONDERFUL film. Truly great!" -> 6 tokens
    assert len(ids) == 6
    assert ids[1] == ids[2] == wd["wonderful"]
    test_rows = list(imdb.test(wd)())
    assert [l for _, l in test_rows] == [0, 1]


def test_wmt14_dict_and_pairs(real_data):
    import paddle_tpu.dataset.common as common
    import paddle_tpu.dataset.wmt14 as wmt14

    assert common.data_source("wmt14") == "real"
    src_dict, trg_dict = wmt14.get_dict(dict_size=100)
    assert src_dict["<s>"] == 0 and src_dict["<e>"] == 1
    assert src_dict["les"] == 3  # line 4 of src.dict
    rows = list(wmt14.train(dict_size=100)())
    assert len(rows) == 3
    src_ids, trg_ids, trg_next = rows[0]
    # "les chats dorment" -> <s> les chats dorment <e>
    assert src_ids == [0, src_dict["les"], src_dict["chats"],
                       src_dict["dorment"], 1]
    # "the cats sleep" -> trg: <s> + ids; next: ids + <e>
    assert trg_ids == [0, trg_dict["the"], trg_dict["cats"],
                       trg_dict["sleep"]]
    assert trg_next == trg_ids[1:] + [1]
    # OOV maps to UNK_ID on a truncated dict
    rows_small = list(wmt14.train(dict_size=4)())
    assert rows_small[0][0][2] == wmt14.UNK_ID  # 'chats' (id 4) now OOV
    assert len(list(wmt14.test(dict_size=100)())) == 1


def test_book_chapter_trains_on_real_idx_bytes(real_data):
    """A book chapter end to end on REAL-format bytes: LeNet-style
    recognize-digits trains from the fixture IDX files; provenance
    reports 'real' (round-4 review item 2 done-bar)."""
    import paddle_tpu
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.framework import Program, program_guard
    import paddle_tpu.dataset.common as common
    import paddle_tpu.dataset.mnist as mnist

    from paddle_tpu.fluid import unique_name

    assert common.data_source("mnist") == "real"
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 7
    # unique_name.guard: the seeded RNG stream is salted with the
    # program's CONTENT (incl. generated var names), so without a fresh
    # name generator the init draw depends on how many vars earlier
    # tests created — one suite ordering handed this test a bad-luck
    # init that didn't clear the loss-decrease bar
    with unique_name.guard():
        with program_guard(prog, startup):
            img = layers.data(name="img", shape=[784], dtype="float32")
            label = layers.data(name="label", shape=[1], dtype="int64")
            hidden = layers.fc(input=img, size=64, act="relu")
            pred = layers.fc(input=hidden, size=10, act="softmax")
            cost = layers.mean(layers.cross_entropy(input=pred, label=label))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(cost)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        reader = paddle_tpu.batch(mnist.train(), batch_size=16)
        feeder = fluid.DataFeeder(place=None, feed_list=[img, label])
        losses = []
        for _ in range(8):  # 8 epochs over the 32-sample fixture
            for data in reader():
                (loss,) = exe.run(prog, feed=feeder.feed(data),
                                  fetch_list=[cost])
                losses.append(float(np.asarray(loss).ravel()[0]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])
