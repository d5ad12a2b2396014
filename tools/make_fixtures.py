"""Generate the tiny byte-GENUINE data fixtures under tests/fixtures/
(round-4 review item 2): real wire formats — gzipped IDX with the 0x803/0x801
magics, a cifar python-pickle tarball, an aclImdb tar fragment, a wmt
sentence-pair tgz — so the real-format parsers are exercised by CI on
actual bytes, not synthetic fallbacks.

Deterministic: run it twice, get identical content (gzip/tar timestamps
pinned to 0). Committed output; re-run only when a format changes."""
import gzip
import io
import os
import pickle
import struct
import tarfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(os.path.dirname(HERE), "tests", "fixtures")


def _gzip_bytes(payload: bytes) -> bytes:
    buf = io.BytesIO()
    # mtime=0: deterministic output
    with gzip.GzipFile(fileobj=buf, mode="wb", mtime=0) as f:
        f.write(payload)
    return buf.getvalue()


def _add_member(tar, name, data: bytes):
    info = tarfile.TarInfo(name)
    info.size = len(data)
    info.mtime = 0
    tar.addfile(info, io.BytesIO(data))


def mnist_images(n):
    """Deterministic pixel pattern: pixel (i, r, c) = (i*7 + r*3 + c) % 256
    — any byte-layout mistake (endianness, header size, row order)
    scrambles it detectably."""
    imgs = np.zeros((n, 28, 28), np.uint8)
    for i in range(n):
        r, c = np.meshgrid(np.arange(28), np.arange(28), indexing="ij")
        imgs[i] = (i * 7 + r * 3 + c) % 256
    return imgs


def make_mnist():
    d = os.path.join(FIXTURES, "mnist")
    os.makedirs(d, exist_ok=True)
    for prefix, n in (("train", 32), ("t10k", 16)):
        imgs = mnist_images(n)
        labels = np.arange(n, dtype=np.uint8) % 10
        # IDX3: magic 0x00000803, count, rows, cols — all big-endian
        img_payload = struct.pack(">IIII", 0x803, n, 28, 28) + imgs.tobytes()
        # IDX1: magic 0x00000801, count
        lbl_payload = struct.pack(">II", 0x801, n) + labels.tobytes()
        with open(os.path.join(d, f"{prefix}-images-idx3-ubyte.gz"),
                  "wb") as f:
            f.write(_gzip_bytes(img_payload))
        with open(os.path.join(d, f"{prefix}-labels-idx1-ubyte.gz"),
                  "wb") as f:
            f.write(_gzip_bytes(lbl_payload))


def make_cifar():
    d = os.path.join(FIXTURES, "cifar")
    os.makedirs(d, exist_ok=True)

    def batch_bytes(n, n_classes, label_key, seed):
        rng = np.random.RandomState(seed)
        data = rng.randint(0, 256, size=(n, 3072)).astype(np.uint8)
        labels = [int(x) for x in rng.randint(0, n_classes, size=n)]
        # py2 pickles carry str (=bytes) keys; protocol 2 matches the era
        return pickle.dumps({b"data": data, label_key: labels}, protocol=2)

    path = os.path.join(d, "cifar-10-python.tar.gz")
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        for i in (1, 2):
            _add_member(tar, f"cifar-10-batches-py/data_batch_{i}",
                        batch_bytes(8, 10, b"labels", seed=40 + i))
        _add_member(tar, "cifar-10-batches-py/test_batch",
                    batch_bytes(8, 10, b"labels", seed=50))
    with open(path, "wb") as f:
        f.write(_gzip_bytes(buf.getvalue()))

    path = os.path.join(d, "cifar-100-python.tar.gz")
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        _add_member(tar, "cifar-100-python/train",
                    batch_bytes(12, 100, b"fine_labels", seed=60))
        _add_member(tar, "cifar-100-python/test",
                    batch_bytes(6, 100, b"fine_labels", seed=61))
    with open(path, "wb") as f:
        f.write(_gzip_bytes(buf.getvalue()))


IMDB_DOCS = {
    # polarity -> (filename, text) — reviews with punctuation/case so the
    # ad-hoc tokenization actually does work
    ("train", "pos"): [
        ("0_9.txt", "A wonderful, WONDERFUL film. Truly great!"),
        ("1_8.txt", "Great acting; a wonderful story."),
    ],
    ("train", "neg"): [
        ("0_1.txt", "Terrible. Just terrible, awful acting."),
        ("1_2.txt", "An awful film -- a terrible story."),
    ],
    ("test", "pos"): [("0_10.txt", "Wonderful story, great film!")],
    ("test", "neg"): [("0_2.txt", "Awful. A terrible film?")],
}


def make_imdb():
    d = os.path.join(FIXTURES, "imdb")
    os.makedirs(d, exist_ok=True)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        for (split, pol), docs in sorted(IMDB_DOCS.items()):
            for fname, text in docs:
                _add_member(tar, f"aclImdb/{split}/{pol}/{fname}",
                            text.encode("utf-8"))
    with open(os.path.join(d, "aclImdb_v1.tar.gz"), "wb") as f:
        f.write(_gzip_bytes(buf.getvalue()))


WMT_SRC_DICT = ["<s>", "<e>", "<unk>", "les", "chats", "dorment", "chiens",
                "mangent", "le", "chat", "dort"]
WMT_TRG_DICT = ["<s>", "<e>", "<unk>", "the", "cats", "sleep", "dogs",
                "eat", "cat", "sleeps"]
WMT_TRAIN = [
    ("les chats dorment", "the cats sleep"),
    ("les chiens mangent", "the dogs eat"),
    ("le chat dort", "the cat sleeps"),
]
WMT_TEST = [("les chiens dorment", "the dogs sleep")]


def make_wmt14():
    d = os.path.join(FIXTURES, "wmt14")
    os.makedirs(d, exist_ok=True)
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tar:
        _add_member(tar, "wmt14/src.dict",
                    ("\n".join(WMT_SRC_DICT) + "\n").encode())
        _add_member(tar, "wmt14/trg.dict",
                    ("\n".join(WMT_TRG_DICT) + "\n").encode())
        _add_member(tar, "wmt14/train/part-00",
                    ("".join(f"{s}\t{t}\n" for s, t in WMT_TRAIN)).encode())
        _add_member(tar, "wmt14/test/part-00",
                    ("".join(f"{s}\t{t}\n" for s, t in WMT_TEST)).encode())
    with open(os.path.join(d, "wmt14.tgz"), "wb") as f:
        f.write(_gzip_bytes(buf.getvalue()))


if __name__ == "__main__":
    make_mnist()
    make_cifar()
    make_imdb()
    make_wmt14()
    total = 0
    for root, _, files in os.walk(FIXTURES):
        for fn in files:
            total += os.path.getsize(os.path.join(root, fn))
    print(f"fixtures written under {FIXTURES} ({total} bytes)")
