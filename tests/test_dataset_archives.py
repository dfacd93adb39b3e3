"""Real-archive parse paths for every dataset module: each test constructs a tiny archive in the REFERENCE's on-disk
format (cifar pickle-tar, aclImdb tar, PTB tgz, ml-1m zip, CoNLL column
files, VOC tar, flowers mats, WMT dict+bitext, LETOR text) and runs the
module's real parser over it — the zero-egress environment cannot
download, but the parsers must not be dead code. MNIST's analog lives
in test_reader_dataset.py::test_mnist_real_archive_parse."""
import gzip
import io
import os
import pickle
import tarfile
import zipfile

import numpy as np
import pytest

from paddle_tpu.dataset import common


@pytest.fixture
def data_home(monkeypatch, tmp_path):
    monkeypatch.setattr(common, "DATA_HOME", str(tmp_path))
    return tmp_path


def _add_bytes(tf, name, payload):
    info = tarfile.TarInfo(name)
    info.size = len(payload)
    tf.addfile(info, io.BytesIO(payload))


def test_cifar10_real_pickle_tar(data_home):
    from paddle_tpu.dataset import cifar
    base = data_home / "cifar"
    os.makedirs(base)
    rng = np.random.RandomState(0)
    with tarfile.open(base / "cifar-10-python.tar.gz", "w:gz") as tf:
        for member, n in (("cifar-10-batches-py/data_batch_1", 6),
                          ("cifar-10-batches-py/data_batch_2", 4),
                          ("cifar-10-batches-py/test_batch", 3)):
            batch = {b"data": rng.randint(0, 256, (n, 3072), dtype=np.uint8),
                     b"labels": [int(l) for l in rng.randint(0, 10, n)]}
            _add_bytes(tf, member, pickle.dumps(batch))
    rows = list(cifar.train10()())
    assert len(rows) == 10          # both data_batch members
    img, lab = rows[0]
    assert img.shape == (3072,) and img.dtype == np.float32
    assert 0.0 <= img.min() and img.max() <= 1.0 and 0 <= lab <= 9
    assert len(list(cifar.test10()())) == 3


def test_cifar100_real_pickle_tar(data_home):
    from paddle_tpu.dataset import cifar
    base = data_home / "cifar"
    os.makedirs(base)
    rng = np.random.RandomState(1)
    with tarfile.open(base / "cifar-100-python.tar.gz", "w:gz") as tf:
        for member, n in (("cifar-100-python/train", 5),
                          ("cifar-100-python/test", 2)):
            batch = {b"data": rng.randint(0, 256, (n, 3072), dtype=np.uint8),
                     b"fine_labels": [int(l) for l in rng.randint(0, 100, n)]}
            _add_bytes(tf, member, pickle.dumps(batch))
    assert len(list(cifar.train100()())) == 5
    rows = list(cifar.test100()())
    assert len(rows) == 2 and 0 <= rows[0][1] <= 99


def test_uci_housing_real_file(data_home):
    from paddle_tpu.dataset import uci_housing
    base = data_home / "uci_housing"
    os.makedirs(base)
    rng = np.random.RandomState(2)
    data = rng.rand(450, 14).astype(np.float32) * 10
    np.savetxt(base / "housing.data", data, fmt="%.4f")
    train = list(uci_housing.train()())
    test = list(uci_housing.test()())
    assert len(train) == 404 and len(test) == 46
    x, y = train[0]
    assert x.shape == (13,) and y.shape == (1,)
    # normalized features: (x - avg) / (max - min) keeps |x| < 1
    assert np.abs(np.stack([t[0] for t in train])).max() < 1.0


def test_imdb_real_aclimdb_tar(data_home):
    from paddle_tpu.dataset import imdb
    base = data_home / "imdb"
    os.makedirs(base)
    docs = {
        "aclImdb/train/pos/0_9.txt": b"A wonderful movie, truly great!",
        "aclImdb/train/pos/1_8.txt": b"Great acting and a great plot.",
        "aclImdb/train/neg/0_2.txt": b"Terrible. Awful pacing, bad jokes.",
        "aclImdb/test/pos/0_7.txt": b"great fun",
        "aclImdb/test/neg/0_3.txt": b"bad, awful",
    }
    with tarfile.open(base / "aclImdb_v1.tar.gz", "w:gz") as tf:
        for name, payload in docs.items():
            _add_bytes(tf, name, payload)
    wd = imdb.word_dict(cutoff=1)    # reference default cutoff is 150;
    assert "<unk>" in wd             # the tiny test corpus needs 1
    assert wd["great"] == 0          # most frequent word gets id 0
    rows = list(imdb.train(wd)())
    assert len(rows) == 3
    labels = [lab for _ids, lab in rows]
    assert labels == [0, 0, 1]       # pos first (0), then neg (1)
    ids, _ = rows[0]
    assert all(0 <= i < len(wd) for i in ids)
    assert len(list(imdb.test()())) == 2


def test_imikolov_real_ptb_tgz(data_home):
    from paddle_tpu.dataset import imikolov
    base = data_home / "imikolov"
    os.makedirs(base)
    train_txt = b"the cat sat on the mat\nthe dog sat\n"
    valid_txt = b"the cat sat\n"
    with tarfile.open(base / "simple-examples.tgz", "w:gz") as tf:
        _add_bytes(tf, "./simple-examples/data/ptb.train.txt", train_txt)
        _add_bytes(tf, "./simple-examples/data/ptb.valid.txt", valid_txt)
    wd = imikolov.build_dict(min_word_freq=1)
    assert wd["the"] == 0 and "<unk>" in wd
    grams = list(imikolov.train(wd, n=3)())
    # sentence 1 has 8 tokens incl <s>/<e> -> 6 trigrams; sentence 2: 3
    assert len(grams) == 6 + 3
    assert all(len(g) == 3 for g in grams)
    src, trg = next(iter(imikolov.train(wd, n=3,
                                        data_type=imikolov.DataType.SEQ)()))
    assert trg[:-1] == src[1:]       # shifted-by-one LM pair
    assert len(list(imikolov.test(wd, n=3)())) == 3


def test_sentiment_real_corpus_dir(data_home):
    from paddle_tpu.dataset import sentiment
    for pol, texts in (("pos", ["good film", "nice good story"]),
                       ("neg", ["bad film", "dull bad script"])):
        d = data_home / "sentiment" / "movie_reviews" / pol
        os.makedirs(d)
        for i, t in enumerate(texts):
            (d / f"cv{i}.txt").write_text(t)
    wd = sentiment.get_word_dict()
    assert "<unk>" in wd and "good" in wd
    rows = list(sentiment.train()())
    # 80% of each polarity's 2 docs -> 1 + 1
    assert len(rows) == 2 and [lab for _i, lab in rows] == [0, 1]
    assert len(list(sentiment.test()())) == 2


def test_movielens_real_ml1m_zip(data_home):
    from paddle_tpu.dataset import movielens
    base = data_home / "movielens"
    os.makedirs(base)
    users = "1::M::25::6::12345\n2::F::50::3::54321\n"
    movies = ("10::Toy Story (1995)::Animation|Comedy\n"
              "20::Heat (1995)::Action\n")
    ratings = "".join(f"{u}::{m}::{r}::97830000{i}\n"
                      for i, (u, m, r) in enumerate(
                          [(1, 10, 5), (1, 20, 3), (2, 10, 4),
                           (2, 20, 2)] * 3))
    with zipfile.ZipFile(base / "ml-1m.zip", "w") as zf:
        zf.writestr("ml-1m/users.dat", users)
        zf.writestr("ml-1m/movies.dat", movies)
        zf.writestr("ml-1m/ratings.dat", ratings)
    assert movielens.max_user_id() == 2
    assert movielens.max_movie_id() == 20
    cats = movielens.movie_categories()
    assert set(cats) == {"Animation", "Comedy", "Action"}
    titles = movielens.get_movie_title_dict()
    assert "toy" in titles and "(1995)" not in titles
    train = list(movielens.train()())
    test = list(movielens.test()())
    assert len(train) + len(test) == 12 and len(test) == 1
    u, gender, age, job, m, cat_ids, title_ids, rating = train[0]
    assert gender == 0 and age == movielens.age_table().index(25)
    assert job == 6 and 1.0 <= rating <= 5.0
    assert all(0 <= c < len(cats) for c in cat_ids)


def test_conll05_real_column_files(data_home):
    from paddle_tpu.dataset import conll05
    base = data_home / "conll05"
    os.makedirs(base)
    (base / "wordDict.txt").write_text(
        "\n".join(["<unk>", "the", "cat", "chased", "a", "mouse"]) + "\n")
    (base / "verbDict.txt").write_text("chase\nrun\n")
    (base / "targetDict.txt").write_text(
        "\n".join(["O", "B-A0", "I-A0", "B-V", "B-A1", "I-A1"]) + "\n")
    words = "The\ncat\nchased\na\nmouse\n\n"
    # one predicate column: (A0 A0) V (A1 A1)
    props = ("-\t(A0*\n-\t*)\nchase\t(V*)\n-\t(A1*\n-\t*)\n\n"
             .replace("\t", " "))
    (base / "test.wsj.words").write_text(words)
    with gzip.open(base / "test.wsj.props.gz", "wt") as f:
        f.write(props)
    rows = list(conll05.test()())
    assert len(rows) == 1
    (word_ids, c_n2, c_n1, c_0, c_p1, c_p2, verb_seq, mark,
     labels) = rows[0]
    wd, vd, ld = conll05.get_dict()
    assert word_ids == [wd[w] for w in
                        ["the", "cat", "chased", "a", "mouse"]]
    assert labels == [ld["B-A0"], ld["I-A0"], ld["B-V"], ld["B-A1"],
                      ld["I-A1"]]
    assert mark == [0, 0, 1, 0, 0]
    assert verb_seq == [vd["chase"]] * 5
    assert c_0 == [wd["chased"]] * 5       # ctx window centered on verb
    assert c_n2 == [wd["the"]] * 5 and c_p2 == [wd["mouse"]] * 5
    assert len(conll05.get_embedding()) == len(wd)


def test_voc2012_real_tar(data_home):
    from PIL import Image
    from paddle_tpu.dataset import voc2012
    base = data_home / "voc2012"
    os.makedirs(base)
    rng = np.random.RandomState(3)

    def png_bytes(arr, palette):
        img = Image.fromarray(arr.astype(np.uint8), mode="P")
        img.putpalette(palette)
        buf = io.BytesIO()
        img.save(buf, format="PNG")
        return buf.getvalue()

    def jpg_bytes(hw):
        img = Image.fromarray(
            rng.randint(0, 256, (hw, hw, 3), dtype=np.uint8))
        buf = io.BytesIO()
        img.save(buf, format="JPEG")
        return buf.getvalue()

    palette = sum(([i, 0, 0] for i in range(256)), [])
    seg = np.zeros((16, 16), np.uint8)
    seg[4:8, 4:8] = 7                      # class 7 blob
    seg[0, 1] = 255                        # VOC void/boundary pixel
    root = "VOCdevkit/VOC2012"
    with tarfile.open(base / "VOCtrainval_11-May-2012.tar", "w") as tf:
        _add_bytes(tf, f"{root}/ImageSets/Segmentation/train.txt",
                   b"2007_000001\n")
        _add_bytes(tf, f"{root}/ImageSets/Segmentation/val.txt",
                   b"2007_000001\n")
        _add_bytes(tf, f"{root}/JPEGImages/2007_000001.jpg", jpg_bytes(16))
        _add_bytes(tf, f"{root}/SegmentationClass/2007_000001.png",
                   png_bytes(seg, palette))
    rows = list(voc2012.train()())
    assert len(rows) == 1
    img, label = rows[0]
    assert img.shape == (3, 16, 16) and img.dtype == np.float32
    assert label.shape == (16, 16) and label[5, 5] == 7 and label[0, 0] == 0
    assert label[0, 1] == 0                # void remapped into range
    assert label.max() < 21


def test_flowers_real_archive_set(data_home):
    from PIL import Image
    from scipy.io import savemat
    from paddle_tpu.dataset import flowers
    base = data_home / "flowers"
    os.makedirs(base)
    rng = np.random.RandomState(4)
    with tarfile.open(base / "102flowers.tgz", "w:gz") as tf:
        for i in (1, 2, 3):
            img = Image.fromarray(
                rng.randint(0, 256, (32, 48, 3), dtype=np.uint8))
            buf = io.BytesIO()
            img.save(buf, format="JPEG")
            _add_bytes(tf, f"jpg/image_{i:05d}.jpg", buf.getvalue())
    savemat(base / "imagelabels.mat",
            {"labels": np.array([[5, 9, 5]], np.float64)})  # 1-based
    savemat(base / "setid.mat", {"trnid": np.array([[1, 3]]),
                                 "valid": np.array([[2]]),
                                 "tstid": np.array([[2]])})
    rows = list(flowers.train()())
    assert len(rows) == 2
    img, lab = rows[0]
    assert img.shape == (3, 224, 224) and lab == 4   # 5 - 1
    assert [lab for _i, lab in list(flowers.valid()())] == [8]


def test_wmt14_real_dict_and_bitext(data_home):
    from paddle_tpu.dataset import wmt14
    base = data_home / "wmt14"
    os.makedirs(base / "train")
    os.makedirs(base / "test")
    (base / "src.dict").write_text(
        "\n".join(["<s>", "<e>", "<unk>", "le", "chat", "noir"]) + "\n")
    (base / "trg.dict").write_text(
        "\n".join(["<s>", "<e>", "<unk>", "the", "cat", "black"]) + "\n")
    (base / "train" / "part-00").write_text(
        "le chat\tthe cat\nle chat noir\tthe black cat\n")
    (base / "test" / "part-00").write_text("le inconnu\tthe dog\n")
    rows = list(wmt14.train()())
    assert len(rows) == 2
    src, trg, trg_next = rows[0]
    sd, td = wmt14.get_dict()
    assert src == [sd["le"], sd["chat"]]
    assert trg == [wmt14.START, td["the"], td["cat"]]
    assert trg_next == [td["the"], td["cat"], wmt14.END]
    # unknown words map to UNK
    (tsrc, _t, _n), = wmt14.test()()
    assert tsrc == [sd["le"], wmt14.UNK]
    rsd, _rtd = wmt14.get_dict(reverse=True)
    assert rsd[sd["chat"]] == "chat"


def test_wmt16_real_parallel_text(data_home):
    from paddle_tpu.dataset import wmt16
    base = data_home / "wmt16"
    os.makedirs(base)
    (base / "train.en").write_text("a cat sat\na dog sat\n")
    (base / "train.de").write_text("eine katze sass\nein hund sass\n")
    (base / "test.en").write_text("a cat\n")
    (base / "test.de").write_text("eine katze\n")
    en = wmt16.get_dict("en", 50)
    de = wmt16.get_dict("de", 50)
    assert en["<s>"] == 0 and en["<e>"] == 1 and en["<unk>"] == 2
    assert en["a"] == 3 and en["sat"] == 4    # frequency order
    rows = list(wmt16.train()())
    assert len(rows) == 2
    src, trg, trg_next = rows[0]
    assert src == [en["a"], en["cat"], en["sat"]]
    assert trg == [wmt16.START, de["eine"], de["katze"], de["sass"]]
    assert trg_next[-1] == wmt16.END
    # dict-size cap truncates the tail into <unk> at lookup time
    tiny = wmt16.get_dict("en", 4)
    assert len(tiny) == 4
    (tsrc, _t, _n), = wmt16.test()()
    assert tsrc == [en["a"], en["cat"]]


def test_mq2007_real_letor_text(data_home, tmp_path):
    from paddle_tpu.dataset import mq2007
    path = tmp_path / "Fold1.txt"
    lines = []
    rng = np.random.RandomState(5)
    for qid, rels in ((10, [2, 0, 1]), (11, [0, 1])):
        for rel in rels:
            feats = " ".join(f"{k}:{rng.rand():.3f}"
                             for k in range(1, 47))
            lines.append(f"{rel} qid:{qid} {feats} #docid = D{qid}-{rel}")
    path.write_text("\n".join(lines) + "\n")
    qlists = mq2007.load_from_text(str(path))
    assert [ql.query_id for ql in qlists] == [10, 11]
    assert len(qlists[0]) == 3 and len(qlists[1]) == 2
    q = qlists[0].querylist[0]
    assert q.relevance_score == 2
    assert q.feature_vector.shape == (mq2007.FEATURE_DIM,)
    assert "docid" in q.description
