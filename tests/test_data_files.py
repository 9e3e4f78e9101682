"""File-backed data path (VERDICT r2 missing item 6): byte-BPE tokenizer,
token shards, array image files — rank-disjoint sharding, exact decode,
checkpointable cursors, and training end-to-end from files."""

from __future__ import annotations

import subprocess
import sys

import numpy as np
import pytest

from easydl_tpu.data import (
    ArrayImageDataset,
    ByteBpeTokenizer,
    TokenFileDataset,
    write_token_shards,
)

CORPUS = (
    "the quick brown fox jumps over the lazy dog\n"
    "the quick brown cat sleeps under the warm sun\n"
    "a lazy dog and a quick cat share the brown rug\n"
) * 20


# ---------------------------------------------------------------- tokenizer

def test_tokenizer_roundtrip_exact():
    tok = ByteBpeTokenizer.train([CORPUS], vocab_size=300)
    for text in (CORPUS, "unseen words étoile 漢字!  double  spaced",
                 " leading space", "tabs\tand\nnewlines"):
        assert tok.decode(tok.encode(text)) == text


def test_tokenizer_compresses_and_persists(tmp_path):
    tok = ByteBpeTokenizer.train([CORPUS], vocab_size=400)
    ids = tok.encode(CORPUS)
    assert len(ids) < len(CORPUS.encode())  # merges actually fired
    assert max(ids) >= 258  # some merged tokens in use
    path = str(tmp_path / "tok.json")
    tok.save(path)
    tok2 = ByteBpeTokenizer.load(path)
    assert tok2.vocab_size == tok.vocab_size
    assert tok2.encode(CORPUS) == ids
    assert tok2.decode(ids) == CORPUS


def test_tokenizer_eos_and_specials():
    tok = ByteBpeTokenizer.train([CORPUS], vocab_size=280)
    ids = tok.encode("hello", append_eos=True)
    assert ids[-1] == tok.eos_id
    assert tok.decode(ids) == "hello"  # specials render as nothing
    assert tok.pad_id != tok.eos_id


# ------------------------------------------------------------ token dataset

def test_token_dataset_shards_disjoint_and_exhaustive(tmp_path):
    ids = np.arange(4096)
    write_token_shards(ids, str(tmp_path), shard_size=1000)  # multi-shard
    seen = []
    for rank in range(2):
        ds = TokenFileDataset(str(tmp_path), batch_size=2, seq_len=15,
                              rank=rank, world=2, seed=7, loop=False)
        for batch in ds:
            assert batch["inputs"].shape == (2, 15)
            # targets are inputs shifted by one
            np.testing.assert_array_equal(batch["inputs"][:, 1:],
                                          batch["targets"][:, :-1])
            seen.extend(batch["inputs"][:, 0].tolist())
    # every window consumed exactly once across ranks (4096 tokens /
    # 16-token windows = 256 windows, all covered, none duplicated)
    assert len(seen) == len(set(seen)) == 256


def test_token_dataset_windows_cross_shard_boundaries(tmp_path):
    ids = np.arange(1000)
    write_token_shards(ids, str(tmp_path), shard_size=333)
    ds = TokenFileDataset(str(tmp_path), batch_size=1, seq_len=99,
                          seed=0, loop=False)
    for batch in ds:
        row = batch["inputs"][0]
        # windows are contiguous runs of the original stream even when they
        # span shard files
        np.testing.assert_array_equal(row, np.arange(row[0], row[0] + 100)[:-1])


def test_token_dataset_cursor_resume(tmp_path):
    write_token_shards(np.arange(8192), str(tmp_path))
    ds1 = TokenFileDataset(str(tmp_path), batch_size=2, seq_len=31, seed=3)
    it1 = iter(ds1)
    got = [next(it1) for _ in range(5)]
    state = ds1.state()
    ds2 = TokenFileDataset(str(tmp_path), batch_size=2, seq_len=31, seed=3)
    ds2.restore_state(state)
    a, b = next(iter(ds2)), next(it1)
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    assert state == {"epoch": 0, "cursor": 5, "world": 1, "batch": 2}
    del got


def test_token_dataset_cursor_rescales_across_reshape(tmp_path):
    """A cursor saved at world=2 restores onto world=4 at the same GLOBAL
    position (elastic scale event between checkpoint and resume)."""
    write_token_shards(np.arange(1 << 14), str(tmp_path))
    ds2 = TokenFileDataset(str(tmp_path), batch_size=4, seq_len=31,
                           rank=0, world=2)
    ds2.cursor = 10  # 10 batches x 4 x world 2 = 80 global windows consumed
    ds4 = TokenFileDataset(str(tmp_path), batch_size=4, seq_len=31,
                           rank=1, world=4)
    ds4.restore_state(ds2.state())
    assert ds4.cursor == 80 // (4 * 4)  # same global position, new shape


def test_token_dataset_epochs_reshuffle(tmp_path):
    write_token_shards(np.arange(2048), str(tmp_path))
    ds = TokenFileDataset(str(tmp_path), batch_size=4, seq_len=15, seed=1,
                          loop=False)
    first_epoch = [b["inputs"][:, 0].tolist() for b in ds]
    ds2 = TokenFileDataset(str(tmp_path), batch_size=4, seq_len=15, seed=1)
    it = iter(ds2)
    second_epoch = []
    for _ in range(2 * ds2.batches_per_epoch):
        b = next(it)
        if ds2.epoch >= 1 or len(second_epoch) < ds2.batches_per_epoch:
            second_epoch.append(b["inputs"][:, 0].tolist())
    assert second_epoch[:ds2.batches_per_epoch] == first_epoch
    assert second_epoch[ds2.batches_per_epoch:] != first_epoch  # reshuffled


# ------------------------------------------------------------ image dataset

def test_image_dataset_shapes_and_sharding(tmp_path):
    np.save(tmp_path / "images.npy",
            np.random.randint(0, 256, (64, 8, 8, 1)).astype(np.uint8))
    np.save(tmp_path / "labels.npy", np.arange(64) % 10)
    seen = []
    for rank in range(2):
        ds = ArrayImageDataset(str(tmp_path), batch_size=4, rank=rank,
                               world=2, loop=False)
        for batch in ds:
            assert batch["image"].shape == (4, 8, 8, 1)
            assert batch["image"].dtype == np.float32
            assert batch["image"].max() <= 1.0  # normalized
            seen.extend(batch["label"].tolist())
    assert len(seen) == 64


# ------------------------------------------- a directory -> its data source
class _Bundle:
    """What ``open_dataset`` reads of a model bundle."""
    name = "stub"

    def __init__(self, seq_len):
        self._seq_len = seq_len

    def make_data(self, batch):
        from types import SimpleNamespace

        return SimpleNamespace(seq_len=self._seq_len) if self._seq_len \
            else object()


def _make_dir(kind, path):
    from easydl_tpu.data import encode_click_tsv

    if kind == "images":
        np.save(path / "images.npy", np.zeros((64, 8, 8, 1), np.uint8))
        np.save(path / "labels.npy", np.arange(64) % 10)
    elif kind == "clicks":
        _write_click_tsv(str(path / "clicks.tsv"))
        encode_click_tsv([str(path / "clicks.tsv")], str(path))
    else:
        write_token_shards(np.arange(8192), str(path))


@pytest.mark.parametrize("kind,cls", [
    ("images", "ArrayImageDataset"), ("clicks", "ClickLogDataset"),
    ("tokens", "TokenFileDataset")])
def test_open_dataset_probes_the_directory_and_restores_the_cursor(
        tmp_path, kind, cls):
    """The one probe the worker, the runner and the evaluator share: the
    class each got from its own copy before, built with the caller's share
    (rank, world, seed, split), and the cursor a checkpoint's metadata
    carried."""
    from easydl_tpu.data import open_dataset, restore_cursor

    _make_dir(kind, tmp_path)
    share = dict(batch_size=2, rank=1, world=2, seed=3, split="train",
                 val_fraction=0.25)
    ds = open_dataset(str(tmp_path), _Bundle(31), **share)
    assert type(ds).__name__ == cls
    assert (ds.batch_size, ds.rank, ds.world, ds.seed) == (2, 1, 2, 3)
    if kind == "tokens":
        assert ds.seq_len == 31  # the model's own ...
        assert open_dataset(str(tmp_path), _Bundle(31), seq_len=15,
                            **share).seq_len == 15  # ... unless stated
        with pytest.raises(ValueError, match="cannot infer seq_len"):
            open_dataset(str(tmp_path), _Bundle(0), **share)
    # the worker's call: no seed, the whole of the defaults
    plain = open_dataset(str(tmp_path), _Bundle(31), batch_size=2)
    assert (plain.rank, plain.world, plain.seed) == (0, 1, 0)

    it = iter(ds)
    for _ in range(3):
        next(it)
    state = ds.state()

    class Ckpt:
        def metadata(self, step):
            return {"metadata": {"data_state": state} if step == 7 else {}}

    resumed = open_dataset(str(tmp_path), _Bundle(31), **share)
    assert restore_cursor(resumed, Ckpt(), 6) is None  # none carried
    assert restore_cursor(resumed, Ckpt(), 7) == state
    a, b = next(iter(resumed)), next(it)
    assert sorted(a) == sorted(b)
    for key in a:
        np.testing.assert_array_equal(a[key], b[key])


def test_runner_turns_a_missing_seq_len_into_its_exit(tmp_path):
    from types import SimpleNamespace

    from easydl_tpu.models.run import file_data

    _make_dir("tokens", tmp_path)
    args = SimpleNamespace(data_dir=str(tmp_path), batch=2, seq_len=0,
                           val_fraction=0.0)
    with pytest.raises(SystemExit, match="--seq-len"):
        file_data(args, _Bundle(0))
    assert file_data(args, _Bundle(31), seed_offset=1).seed == 1


# ------------------------------------------------------------- end-to-end

def test_encode_cli_and_training_from_files(tmp_path, eight_devices):
    """Full path: corpus -> trained tokenizer -> shards -> gpt trains on it
    through the zoo runner's --data-dir."""
    corpus = tmp_path / "corpus.txt"
    corpus.write_text(CORPUS)
    tok_path = tmp_path / "tok.json"
    shards = tmp_path / "shards"
    for cmd in (
        [sys.executable, "-m", "easydl_tpu.data.encode", str(corpus),
         "--tokenizer", str(tok_path), "--train-tokenizer",
         "--vocab-size", "384"],
        [sys.executable, "-m", "easydl_tpu.data.encode", str(corpus),
         "--tokenizer", str(tok_path), "--out", str(shards)],
    ):
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr

    ds = TokenFileDataset(str(shards), batch_size=4, seq_len=32)
    batch = next(iter(ds))
    tok = ByteBpeTokenizer.load(str(tok_path))
    assert batch["inputs"].max() < tok.vocab_size

    # the zoo runner trains a tiny gpt from these files
    from easydl_tpu.models.run import main as run_main

    argv = sys.argv
    sys.argv = [
        "run", "--model", "gpt", "--steps", "4", "--batch", "8",
        "--data-dir", str(shards), "--seq-len", "32",
        "--model-arg", "size=test", "--model-arg", "seq_len=32",
        "--model-arg", f"vocab={tok.vocab_size}",
    ]
    try:
        run_main()
    finally:
        sys.argv = argv


def _idx_bytes(arr: np.ndarray) -> bytes:
    """Serialize an array into the IDX wire format (ubyte payload)."""
    header = bytes([0, 0, 0x08, arr.ndim])
    for d in arr.shape:
        header += int(d).to_bytes(4, "big")
    return header + arr.astype(np.uint8).tobytes()


def test_mnist_idx_import_and_training(tmp_path, eight_devices):
    """BASELINE config 1 from the wire format it actually ships in: generate
    MNIST IDX bytes (images gzipped, labels plain — both spellings occur in
    the wild), import via the CLI, train the MLP from the output
    (VERDICT r3 missing 3)."""
    import gzip

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (64, 28, 28), dtype=np.uint8)
    labels = (np.arange(64) % 10).astype(np.uint8)
    src = tmp_path / "raw"
    src.mkdir()
    with gzip.open(src / "train-images-idx3-ubyte.gz", "wb") as f:
        f.write(_idx_bytes(images))
    (src / "train-labels-idx1-ubyte").write_bytes(_idx_bytes(labels))

    out = tmp_path / "mnist"
    res = subprocess.run(
        [sys.executable, "-m", "easydl_tpu.data.images", "mnist", str(src),
         "--out", str(out)],
        capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr

    got = np.load(out / "images.npy")
    assert got.shape == (64, 28, 28, 1) and got.dtype == np.uint8
    np.testing.assert_array_equal(got[..., 0], images)
    np.testing.assert_array_equal(np.load(out / "labels.npy"), labels)

    from easydl_tpu.models.run import main as run_main

    argv = sys.argv
    sys.argv = [
        "run", "--model", "mlp", "--steps", "3", "--batch", "8",
        "--data-dir", str(out),
        "--model-arg", "input_shape=[28,28,1]",
        "--model-arg", "features=[32,32]",
    ]
    try:
        run_main()
    finally:
        sys.argv = argv


def test_image_folder_import(tmp_path):
    """Class-per-subdirectory tree -> arrays + stable classes.json; junk
    files are skipped, not fatal."""
    from PIL import Image

    from easydl_tpu.data import import_image_folder

    src = tmp_path / "tree"
    for cls, color in (("cat", (255, 0, 0)), ("dog", (0, 0, 255))):
        (src / cls).mkdir(parents=True)
        for i in range(3):
            Image.new("RGB", (10 + i, 12), color).save(
                src / cls / f"im{i}.png")
    (src / "cat" / "notes.txt").write_text("not an image")
    (src / "dog" / "broken.png").write_bytes(b"\x89PNG junk")

    n, classes = import_image_folder(str(src), str(tmp_path / "out"),
                                     size=(8, 8))
    assert classes == ["cat", "dog"]
    assert n == 6  # broken.png skipped, notes.txt ignored
    images = np.load(tmp_path / "out" / "images.npy")
    labels = np.load(tmp_path / "out" / "labels.npy")
    assert images.shape == (6, 8, 8, 3)
    # red images labelled cat(0), blue dog(1)
    assert [int(x) for x in labels] == [0, 0, 0, 1, 1, 1]
    assert images[0, 0, 0, 0] > 200 and images[-1, 0, 0, 2] > 200

    ds = ArrayImageDataset(str(tmp_path / "out"), batch_size=2, loop=False)
    batch = next(iter(ds))
    assert batch["image"].shape == (2, 8, 8, 3)


def test_elastic_cfg_forwards_data_dir():
    """--data-dir must survive the trainer's command parse (the elastic
    workers read it from the worker config, not argv)."""
    from easydl_tpu.elastic.trainer_main import parse_runner_command

    ns, _ = parse_runner_command(
        "python -m easydl_tpu.models.run --model gpt "
        "--data-dir /data/tok --seq-len 64"
    )
    assert ns.data_dir == "/data/tok" and ns.seq_len == 64


def test_token_dataset_val_split_disjoint_and_stable(tmp_path):
    """--val-fraction holdout: train and val windows are disjoint, cover
    everything, and the assignment is stable across seeds/epochs (no leak)."""
    write_token_shards(np.arange(1 << 14), str(tmp_path))
    train = TokenFileDataset(str(tmp_path), batch_size=4, seq_len=31,
                             seed=0, val_fraction=0.25, split="train")
    val = TokenFileDataset(str(tmp_path), batch_size=4, seq_len=31,
                           seed=99, val_fraction=0.25, split="val")
    t, v = set(train._windows.tolist()), set(val._windows.tolist())
    assert not (t & v)
    assert len(t | v) == train.num_windows
    assert 0.15 < len(v) / train.num_windows < 0.35
    # different seed, same assignment (the split hash ignores the seed)
    val2 = TokenFileDataset(str(tmp_path), batch_size=4, seq_len=31,
                            seed=0, val_fraction=0.25, split="val")
    assert set(val2._windows.tolist()) == v
    with pytest.raises(ValueError):
        TokenFileDataset(str(tmp_path), batch_size=4, seq_len=31,
                         split="val")  # val requires a fraction


# ------------------------------------------------------------- click logs

def _write_click_tsv(path, n=64, num_dense=13, num_sparse=26):
    rng = np.random.RandomState(5)
    with open(path, "w") as f:
        for i in range(n):
            dense = [str(rng.randint(0, 100)) if rng.rand() > 0.1 else ""
                     for _ in range(num_dense)]
            cats = ["%08x" % rng.randint(0, 1 << 30) if rng.rand() > 0.1
                    else "" for _ in range(num_sparse)]
            f.write("\t".join([str(i % 2)] + dense + cats) + "\n")


def test_click_tsv_encode_and_dataset(tmp_path):
    from easydl_tpu.data import ClickLogDataset, encode_click_tsv

    tsv = tmp_path / "clicks.tsv"
    _write_click_tsv(str(tsv))
    n = encode_click_tsv([str(tsv)], str(tmp_path / "enc"))
    assert n == 64
    ds = ClickLogDataset(str(tmp_path / "enc"), batch_size=8, loop=False)
    total = 0
    for batch in ds:
        assert batch["sparse_ids"].shape == (8, 26)
        assert batch["sparse_ids"].dtype == np.int64
        assert batch["dense"].shape == (8, 13)
        assert (batch["dense"] >= 0).all()  # log1p of clamped counts
        assert set(np.unique(batch["label"])) <= {0.0, 1.0}
        total += 8
    assert total == 64
    # missing/malformed tokens mapped deterministically: re-encode matches
    encode_click_tsv([str(tsv)], str(tmp_path / "enc2"))
    np.testing.assert_array_equal(
        np.load(tmp_path / "enc" / "sparse.npy"),
        np.load(tmp_path / "enc2" / "sparse.npy"))


def test_click_dataset_trains_deepfm_through_runner(tmp_path, eight_devices):
    from easydl_tpu.data import encode_click_tsv

    tsv = tmp_path / "clicks.tsv"
    _write_click_tsv(str(tsv), n=128)
    encode_click_tsv([str(tsv)], str(tmp_path / "enc"))

    from easydl_tpu.models.run import main as run_main

    argv = sys.argv
    sys.argv = [
        "run", "--model", "deepfm", "--steps", "3", "--batch", "16",
        "--data-dir", str(tmp_path / "enc"),
        "--model-arg", "vocab=1024", "--model-arg", "dim=4",
    ]
    try:
        run_main()
    finally:
        sys.argv = argv


def test_bert_trains_from_token_shards(tmp_path, eight_devices):
    """BERT's masked-LM loss reads only batch['inputs'] (masking happens
    inside the jitted loss), so the same token shards feed it unchanged —
    every LM family consumes the one file format."""
    write_token_shards(np.arange(4096) % 300, str(tmp_path))

    from easydl_tpu.models.run import main as run_main

    argv = sys.argv
    sys.argv = [
        "run", "--model", "bert", "--steps", "3", "--batch", "8",
        "--data-dir", str(tmp_path), "--seq-len", "32",
        "--model-arg", "size=test", "--model-arg", "seq_len=32",
        "--model-arg", "vocab=384",
    ]
    try:
        run_main()
    finally:
        sys.argv = argv
