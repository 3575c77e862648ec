"""Dataset-ingestion subsystem tests: IDX codec properties, LEAF
roundtrips, encoding invariants, registry/mirror identity, natural
partitioning, and the golden ClientData digest.

Everything here runs offline against a tmp ``--data-dir`` (the CI
``data-offline`` job runs exactly this file with no network).
"""
import gzip
import hashlib
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import partition
from repro.data.ingest import encode, idx, leaf, natural, registry

# ---------------------------------------------------------------------------
# IDX codec: write→read roundtrip property tests
# ---------------------------------------------------------------------------

_DTYPES = (np.uint8, np.int8, np.int16, np.int32, np.float32, np.float64)


def _random_array(rng, dtype):
    ndim = int(rng.integers(1, 4))
    shape = tuple(int(rng.integers(1, 7)) for _ in range(ndim))
    a = rng.normal(scale=50.0, size=shape)
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        a = np.clip(np.rint(a), info.min, info.max)
    return a.astype(dtype)


def test_idx_bytes_roundtrip_bit_exact_random_shapes():
    """decode(encode(a)) == a — every dtype code, random shapes; and the
    metered size is exactly len(buffer) (header + dims + payload)."""
    rng = np.random.default_rng(0)
    for _ in range(60):
        a = _random_array(rng, _DTYPES[int(rng.integers(len(_DTYPES)))])
        buf = idx.encode(a)
        assert len(buf) == 4 + 4 * a.ndim + a.size * a.dtype.itemsize
        out = idx.decode(buf)
        assert out.dtype == a.dtype and out.shape == a.shape
        assert (out == a).all() or \
            (np.isnan(out) == np.isnan(a)).all()  # float NaN payloads


@pytest.mark.parametrize("gz", [False, True])
def test_idx_file_roundtrip_gzip_on_off(tmp_path, gz):
    rng = np.random.default_rng(1)
    for i in range(8):
        a = _random_array(rng, _DTYPES[i % len(_DTYPES)])
        path = tmp_path / (f"a{i}.idx.gz" if gz else f"a{i}.idx")
        idx.write(path, a)
        out = idx.read(path)
        assert out.dtype == a.dtype and (out == a).all()
        # sidecar written and verified on read
        assert idx.checksum_path(path).exists()


def test_idx_gzip_sniffed_without_suffix(tmp_path):
    """A gzipped file without the .gz suffix still parses (magic sniff)."""
    a = np.arange(24, dtype=np.int16).reshape(4, 6)
    plain = tmp_path / "plain"
    plain.write_bytes(gzip.compress(idx.encode(a)))
    assert (idx.read(plain) == a).all()


def test_idx_corrupted_checksum_rejected(tmp_path):
    path = idx.write(tmp_path / "x.gz", np.arange(100, dtype=np.uint8))
    raw = bytearray(path.read_bytes())
    raw[-1] ^= 0xFF
    path.write_bytes(bytes(raw))
    with pytest.raises(idx.ChecksumError, match="checksum mismatch"):
        idx.read(path)
    # verification is the gate: skipping it surfaces the gzip/IDX error
    with pytest.raises(Exception):
        idx.read(path, verify=False)


def test_idx_malformed_rejected():
    a = np.arange(6, dtype=np.uint8)
    buf = idx.encode(a)
    with pytest.raises(idx.IDXFormatError, match="magic"):
        idx.decode(b"\x01" + buf[1:])
    with pytest.raises(idx.IDXFormatError, match="dtype code"):
        idx.decode(buf[:2] + b"\x42" + buf[3:])
    with pytest.raises(idx.IDXFormatError):
        idx.decode(buf[:-1])                     # truncated payload
    with pytest.raises(idx.IDXFormatError):
        idx.decode(buf + b"\x00")                # trailing garbage
    with pytest.raises(idx.IDXFormatError):
        idx.encode(np.arange(4, dtype=np.uint16))  # no IDX code


# ---------------------------------------------------------------------------
# LEAF shards
# ---------------------------------------------------------------------------

def test_leaf_write_read_roundtrip(tmp_path):
    rng = np.random.default_rng(3)
    users = [f"w{i}" for i in range(7)]
    xs = [rng.random((int(rng.integers(2, 9)), 16)).astype(np.float32)
          for _ in users]
    ys = [rng.integers(0, 62, size=len(x)).astype(np.int32) for x in xs]
    paths = leaf.write_shards(tmp_path, users, xs, ys, writers_per_shard=3)
    assert len(paths) == 3                       # 7 writers / 3 per shard
    pool = leaf.read_shards(tmp_path)
    assert pool.users == tuple(users)
    for i in range(len(users)):
        rows = pool.writers == i
        assert (pool.y[rows] == ys[i]).all()
        assert np.allclose(pool.x[rows], xs[i])  # repr-float JSON roundtrip
        assert (pool.x[rows] == xs[i].astype(np.float32)).all()


# ---------------------------------------------------------------------------
# encodings: thermometer invariants, quantile, jit-ability
# ---------------------------------------------------------------------------

def test_thermometer_monotone_and_level_counts():
    """Bit k is monotone in x; bits-per-pixel == thresholds passed; the
    layout is feature-major with exactly ``levels`` bits per feature."""
    levels = 5
    enc = encode.Thermometer(levels=levels)
    x = jnp.linspace(0.0, 1.0, 13)[:, None]      # (13, 1) increasing
    bits = np.asarray(enc(x))
    assert bits.shape == (13, levels)
    assert enc.out_features(7) == 7 * levels
    # monotone: a larger pixel never clears a bit a smaller one set
    assert (np.diff(bits.astype(np.int32), axis=0) >= 0).all()
    # per-pixel popcount equals the number of thresholds passed
    th = np.asarray(enc.thresholds)
    expect = (np.asarray(x) >= th[None, :]).sum(axis=1)
    assert (bits.sum(axis=1) == expect).all()
    # thermometer property: bits are a prefix (1s then 0s) per pixel
    assert (np.sort(bits, axis=1)[:, ::-1] == bits).all()


def test_quantile_fits_pool_and_balances_bits():
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.random((400, 6)) ** 3)   # skewed pixels
    enc = encode.Quantile.fit(pool, levels=4)
    bits = np.asarray(enc(pool))
    assert bits.shape == (400, 24)
    # each fitted threshold splits the pool near its quantile
    rates = bits.reshape(400, 6, 4).mean(axis=0)
    expect = 1.0 - (np.arange(1, 5) / 5.0)
    assert np.abs(rates - expect[None, :]).max() < 0.05


def test_encodings_are_jit_able_and_composable():
    x = jnp.asarray(np.random.default_rng(6).random((5, 9)), jnp.float32)
    for enc in (encode.Booleanize(0.4), encode.Thermometer(3),
                encode.Quantile.fit(x, 2),
                encode.Pipeline((encode.Thermometer(2),))):
        eager = np.asarray(enc(x))
        jitted = np.asarray(jax.jit(enc.__call__)(x))
        assert (eager == jitted).all()
        assert eager.shape[1] == enc.out_features(9)
        assert eager.dtype == np.uint8


def test_encoding_spec_parser():
    assert encode.build("bool").threshold == 0.5
    assert encode.build("bool:0.3").threshold == 0.3
    assert encode.build("thermometer:7").levels == 7
    q = encode.build("quantile:3", pool=jnp.ones((10, 4)))
    assert q.levels == 3
    with pytest.raises(ValueError, match="unknown encoding"):
        encode.build("onehot")
    with pytest.raises(ValueError, match="needs the pool"):
        encode.build("quantile:3")


# ---------------------------------------------------------------------------
# registry + offline mirror
# ---------------------------------------------------------------------------

def test_registry_unknown_name_lists_choices():
    with pytest.raises(ValueError, match="synthmnist"):
        registry.load("mnist2", None)
    assert set(registry.SYNTH_DATASETS) <= set(registry.names())
    assert set(registry.REAL_DATASETS) <= set(registry.names())


def test_real_flavour_requires_data_dir():
    with pytest.raises(ValueError, match="file-backed"):
        registry.load("mnist", None)


def test_synth_names_are_the_single_source_of_truth():
    from repro.data import synthetic
    assert synthetic.DATASETS is registry.SYNTH_DATASETS


def test_mirror_written_and_preexisting_files_load_identically(tmp_path):
    """First load writes the mirror and parses it; second load parses
    the now pre-existing files — pools must be bit-identical (the pool
    is a pure function of the file bytes).  The in-memory synthetic
    fallback agrees too (the mirror stores the same bits as 0/255)."""
    kw = dict(side=10, n_samples=300, seed=4)
    first = registry.load("synthmnist", tmp_path, **kw)
    second = registry.load("synthmnist", tmp_path, **kw)
    memory = registry.load("synthmnist", None, **kw)
    for a, b in ((first, second), (first, memory)):
        assert (np.asarray(a.x) == np.asarray(b.x)).all()
        assert (np.asarray(a.y) == np.asarray(b.y)).all()
    assert first.n_features == 100 and first.writers is None


def test_leaf_mirror_identity_and_writer_tags(tmp_path):
    kw = dict(side=8, n_samples=300, seed=5, n_writers=9)
    first = registry.load("synthfemnist", tmp_path, **kw)
    second = registry.load("synthfemnist", tmp_path, **kw)
    assert (np.asarray(first.x) == np.asarray(second.x)).all()
    assert (np.asarray(first.writers) == np.asarray(second.writers)).all()
    assert first.n_classes == 62
    sizes = np.bincount(np.asarray(first.writers))
    assert len(sizes) == 9 and len(set(sizes.tolist())) > 1  # heterogeneous


def test_partial_idx_pair_is_rejected_not_overwritten(tmp_path):
    """A lone (possibly real) images file must never be silently paired
    with mirror-written synthetic labels — or worse, overwritten."""
    root = tmp_path / "mnist"
    target = root / "train-images-idx3-ubyte.gz"
    idx.write(target, np.zeros((3, 28, 28), np.uint8))
    before = target.read_bytes()
    with pytest.raises(FileNotFoundError, match="partial train IDX pair"):
        registry.load("mnist", tmp_path, n_samples=50, seed=0)
    assert target.read_bytes() == before        # untouched


def test_leaf_malformed_shards_rejected(tmp_path):
    users = ["wa", "wb"]
    xs = [np.zeros((2, 4), np.float32), np.ones((3, 4), np.float32)]
    ys = [np.zeros(2, np.int32), np.ones(3, np.int32)]
    leaf.write_shards(tmp_path, users, xs, ys)
    import json
    path = tmp_path / "all_data_0.json"
    shard = json.loads(path.read_text())

    missing = dict(shard, user_data={"wa": shard["user_data"]["wa"]})
    path.write_text(json.dumps(missing))
    idx.write_checksum(path)
    with pytest.raises(leaf.LeafFormatError, match="missing from"):
        leaf.read_shards(tmp_path)

    lying = dict(shard, num_samples=[2, 99])
    path.write_text(json.dumps(lying))
    idx.write_checksum(path)
    with pytest.raises(leaf.LeafFormatError, match="declares"):
        leaf.read_shards(tmp_path)


def test_ambiguous_gz_and_plain_pair_is_rejected(tmp_path):
    """A mirror .gz next to a plain real drop-in must fail loudly, not
    silently shadow one of them."""
    registry.load("synthmnist", tmp_path, side=8, n_samples=100, seed=0)
    root = tmp_path / "synthmnist"
    idx.write(root / "train-images-idx3-ubyte",
              np.zeros((2, 8, 8), np.uint8))
    with pytest.raises(FileExistsError, match="remove the one"):
        registry.load("synthmnist", tmp_path, side=8, n_samples=100,
                      seed=0)


def test_t10k_without_train_pair_refuses_mirror(tmp_path):
    """A real held-out pair with no train pair must not be silently
    completed with synthetic mirror train data."""
    root = tmp_path / "mnist"
    idx.write(root / "t10k-images-idx3-ubyte.gz",
              np.zeros((2, 28, 28), np.uint8))
    idx.write(root / "t10k-labels-idx1-ubyte.gz",
              np.zeros((2,), np.uint8))
    with pytest.raises(FileNotFoundError, match="refuses"):
        registry.load("mnist", tmp_path, n_samples=50, seed=0)
    assert not (root / "train-images-idx3-ubyte.gz").exists()


def test_partial_t10k_pair_is_rejected(tmp_path):
    registry.load("synthmnist", tmp_path, side=8, n_samples=100, seed=0)
    idx.write(tmp_path / "synthmnist" / "t10k-images-idx3-ubyte.gz",
              np.zeros((2, 8, 8), np.uint8))
    with pytest.raises(FileNotFoundError, match="partial t10k"):
        registry.load("synthmnist", tmp_path, side=8, n_samples=100,
                      seed=0)


def test_corrupted_cache_is_rejected_at_load(tmp_path):
    registry.load("synthmnist", tmp_path, side=8, n_samples=100, seed=0)
    target = tmp_path / "synthmnist" / "train-images-idx3-ubyte.gz"
    raw = bytearray(target.read_bytes())
    raw[-1] ^= 0xFF
    target.write_bytes(bytes(raw))
    with pytest.raises(idx.ChecksumError):
        registry.load("synthmnist", tmp_path, side=8, n_samples=100, seed=0)


# ---------------------------------------------------------------------------
# natural (writer-identity) partitioning
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def femnist_pool(tmp_path_factory):
    root = tmp_path_factory.mktemp("leafcache")
    return registry.load("synthfemnist", root, side=8, n_samples=600,
                         seed=6, n_writers=12)


def test_natural_partition_contract(femnist_pool):
    cd = natural.partition_writers(femnist_pool, n_clients=5, n_train=24,
                                   n_test=8, n_conf=8,
                                   key=jax.random.PRNGKey(0))
    assert cd.x_train.shape == (5, 24, femnist_pool.n_features)
    assert cd.x_conf.shape == (5, 8, femnist_pool.n_features)
    # real heterogeneous deployment sizes, summing to the pool
    sizes = np.asarray(cd.sizes)
    assert sizes.sum() == femnist_pool.x.shape[0]
    assert len(set(sizes.tolist())) > 1
    # mixtures are the true label histograms (rows sum to 1)
    assert np.allclose(np.asarray(cd.mixtures).sum(axis=1), 1.0, atol=1e-5)
    # deterministic
    cd2 = natural.partition_writers(femnist_pool, n_clients=5, n_train=24,
                                    n_test=8, n_conf=8,
                                    key=jax.random.PRNGKey(0))
    assert (np.asarray(cd.y_train) == np.asarray(cd2.y_train)).all()


def test_natural_partition_samples_stay_within_writer_group(femnist_pool):
    """Every row a client holds belongs to one of its writers — the
    non-IID structure is real, not resampled across clients."""
    n_clients = 4
    cd = natural.partition_writers(femnist_pool, n_clients=n_clients,
                                   n_train=16, n_test=8, n_conf=8,
                                   key=jax.random.PRNGKey(1))
    writers = np.asarray(femnist_pool.writers)
    x = np.asarray(femnist_pool.x)
    groups = np.array_split(np.unique(writers), n_clients)
    for i in range(n_clients):
        rows = x[np.isin(writers, groups[i])]
        for split in (cd.x_train, cd.x_test, cd.x_conf):
            for sample in np.asarray(split[i]):
                assert (rows == sample[None, :]).all(axis=1).any()


def test_natural_partition_padding_never_leaks_train_into_eval():
    """A client whose writers hold fewer rows than the budget is padded
    by wraparound — but only within the training split: no test/conf
    row may also appear in x_train (eval integrity under padding)."""
    rng = np.random.default_rng(9)
    n_writers, f = 6, 12
    # continuous unique-ish rows so byte equality == same pool row
    xs = [rng.random((int(n), f)).astype(np.float32)
          for n in (3, 5, 4, 30, 3, 6)]      # mostly tiny writers
    ys = [rng.integers(0, 5, size=len(x)).astype(np.int32) for x in xs]
    pool = registry.Pool(
        x=jnp.asarray(np.concatenate(xs)),
        y=jnp.asarray(np.concatenate(ys)),
        writers=jnp.asarray(np.concatenate(
            [np.full(len(x), w, np.int32) for w, x in enumerate(xs)])),
        n_classes=5, n_features=f, name="tiny")
    cd = natural.partition_writers(pool, n_clients=n_writers, n_train=16,
                                   n_test=8, n_conf=8,
                                   key=jax.random.PRNGKey(2))
    for i in range(n_writers):
        train = {np.asarray(s).tobytes()
                 for s in np.asarray(cd.x_train[i])}
        for split in (cd.x_test, cd.x_conf):
            for s in np.asarray(split[i]):
                assert s.tobytes() not in train, f"client {i} leaked"


def test_natural_partition_needs_enough_writers(femnist_pool):
    with pytest.raises(ValueError, match="writers"):
        natural.partition_writers(femnist_pool, n_clients=13, n_train=4,
                                  n_test=2, n_conf=2,
                                  key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="no writer identities"):
        natural.partition_writers(
            registry.load("synthmnist", None, side=8, n_samples=50),
            n_clients=2, n_train=4, n_test=2, n_conf=2,
            key=jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# golden digest: the full parse→encode→partition chain, pinned
# ---------------------------------------------------------------------------

def _digest(tree) -> str:
    h = hashlib.sha256()
    for arr in jax.tree_util.tree_leaves(tree):
        a = np.asarray(arr)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 over every ClientData leaf (dtype + shape + bytes) of the
# load("synthmnist")→dirichlet_clients chain below, as produced by the
# threefry PRNG under the installed JAX's default key stream
# (jax_threefry_partitionable=True).  If a jax upgrade legitimately
# changes a sampler,
# regenerate with: PYTHONPATH=src python -c "from tests.test_ingest
# import _golden; print(_golden(None))"  (pass a tmp dir to pin the
# file path too).
GOLDEN_SYNTHMNIST_CLIENTDATA = (
    "2034421175d46e128c101775c9522a0ca5fedd4d0ca20f6d947245340f563902")


def _golden(data_dir) -> str:
    pool = registry.load("synthmnist", data_dir, side=10, n_samples=400,
                         seed=0)
    cd = partition.dirichlet_clients(
        pool.x, pool.y, pool.n_classes, n_clients=4, experiment=5,
        key=jax.random.PRNGKey(1), n_train=20, n_test=10, n_conf=10)
    return _digest(cd)


def test_golden_synthmnist_clientdata_digest(tmp_path):
    """load("synthmnist") → ClientData is bit-identical to the committed
    digest — through the file path (mirror write → IDX parse → encode →
    Dirichlet partition) *and* the in-memory fallback."""
    assert _golden(tmp_path) == GOLDEN_SYNTHMNIST_CLIENTDATA
    assert _golden(None) == GOLDEN_SYNTHMNIST_CLIENTDATA


# ---------------------------------------------------------------------------
# streaming ingestion: per-writer shards on demand, no pool
# ---------------------------------------------------------------------------

# sha256 over every gathered-ClientData leaf of the streaming chain
# below (load_stream → StreamingClientData.gather_clients over the full
# population) — the SAME digest the materialized chain (load →
# partition_writers) produces, pinning that on-demand shard reads
# reproduce the committed pool-backed partition bit for bit.
# Regenerate (e.g. after a legitimate sampler change) with:
#   PYTHONPATH=src python -c "from tests.test_ingest import \
#     _golden_stream; print(_golden_stream())"
GOLDEN_SYNTHFEMNIST_STREAM = (
    "d4d6ae4cf7347a341583161dded2db6033b0011d7e014fcafb59838fe1dcbf84")

_STREAM_KW = dict(side=8, n_samples=600, seed=6, n_writers=12)
_BUDGET = dict(n_clients=5, n_train=24, n_test=8, n_conf=8)


@pytest.fixture(scope="module")
def femnist_stream(tmp_path_factory):
    """Mirror root shared by the materialized pool (the reference) and
    the streaming writer table over the same shard files."""
    root = tmp_path_factory.mktemp("leafstream")
    pool = registry.load("synthfemnist", root, **_STREAM_KW)
    spool = registry.load_stream("synthfemnist", root, **_STREAM_KW)
    return pool, spool


def _golden_stream(root=None) -> str:
    import tempfile

    from repro.fl.store import StreamingClientData
    root = root or tempfile.mkdtemp(prefix="leafstream_golden_")
    spool = registry.load_stream("synthfemnist", root, **_STREAM_KW)
    sdata = StreamingClientData(spool, key=jax.random.PRNGKey(0),
                                **_BUDGET)
    return _digest(sdata.gather_clients(np.arange(_BUDGET["n_clients"])))


def test_streaming_gather_matches_materialized_partition(femnist_stream):
    """``StreamingClientData.gather_clients`` == ``partition_writers``
    field for field: the on-demand per-writer shard loads reproduce the
    pool-backed natural partition bit for bit — full population, and
    any subset equals the full gather sliced at its ids."""
    from repro.fl.store import StreamingClientData
    pool, spool = femnist_stream
    cd = natural.partition_writers(pool, key=jax.random.PRNGKey(0),
                                   **_BUDGET)
    sdata = StreamingClientData(spool, key=jax.random.PRNGKey(0),
                                **_BUDGET)
    full = sdata.gather_clients(np.arange(5))
    for la, lb in zip(jax.tree_util.tree_leaves(cd),
                      jax.tree_util.tree_leaves(full)):
        assert (np.asarray(la) == np.asarray(lb)).all()
    assert _digest(full) == _digest(cd)
    # the O(N) scheduler table is the partition's real size table
    assert (np.asarray(sdata.sizes) == np.asarray(cd.sizes)).all()
    sub = sdata.gather_clients(np.asarray([3, 1]))
    for la, lb in zip(jax.tree_util.tree_leaves(sub),
                      jax.tree_util.tree_leaves(full)):
        assert (np.asarray(la) == np.asarray(lb)[[3, 1]]).all()


def test_streaming_golden_digest(femnist_stream):
    """The streaming chain is bit-identical to the committed digest —
    mirror write → shard index → on-demand parse → encode → budgeted
    split, pinned against drift exactly like the synthmnist golden."""
    _, spool = femnist_stream
    from repro.fl.store import StreamingClientData
    sdata = StreamingClientData(spool, key=jax.random.PRNGKey(0),
                                **_BUDGET)
    got = _digest(sdata.gather_clients(np.arange(5)))
    assert got == GOLDEN_SYNTHFEMNIST_STREAM


def test_streaming_parses_only_needed_shards_and_never_the_pool(
        femnist_stream, monkeypatch):
    """The O(K) ingestion contract: gathering one client parses only
    the shard(s) holding its writers (counting shim on the shard
    parser), and full-pool materialization (``leaf.read_shards``) is
    never triggered."""
    from repro.fl.store import StreamingClientData
    _, spool = femnist_stream
    index = leaf.ensure_index(spool.root)      # index already built
    assert len(index["shards"]) == 2           # 12 writers, 10 per shard

    calls = []
    real_parse = leaf._parse_shard
    monkeypatch.setattr(
        leaf, "_parse_shard",
        lambda path, verify=True: (calls.append(pathlib.Path(path).name),
                                   real_parse(path, verify))[1])
    monkeypatch.setattr(
        leaf, "read_shards",
        lambda *a, **k: (_ for _ in ()).throw(AssertionError(
            "streaming gather materialized the full pool")))

    sdata = StreamingClientData(spool, key=jax.random.PRNGKey(0),
                                **_BUDGET)
    sdata.gather_clients(np.asarray([0]))      # writers 0-2: shard 0 only
    assert calls == ["all_data_0.json"]

    calls.clear()
    sdata.gather_clients(np.asarray([4]))      # writers 10-11: shard 1
    assert calls == ["all_data_1.json"]


def test_streaming_index_staleness_is_loud_and_rebuildable(
        femnist_stream, tmp_path):
    """A shard set that drifted under an existing index fails loudly —
    a stale index would silently mis-route writer ids to the wrong
    shards — and deleting the index rebuilds it over the current
    shard set."""
    import shutil
    _, spool = femnist_stream
    root = tmp_path / "drift"
    shutil.copytree(spool.root, root)
    before = leaf.ensure_index(root)
    src = root / "all_data_1.json"
    dup = root / "all_data_2.json"
    shutil.copy(src, dup)
    shutil.copy(idx.checksum_path(src), idx.checksum_path(dup))
    with pytest.raises(leaf.LeafFormatError, match="stale"):
        leaf.read_index(root)
    (root / leaf.INDEX_NAME).unlink()
    idx.checksum_path(root / leaf.INDEX_NAME).unlink()
    after = leaf.ensure_index(root)
    assert len(after["shards"]) == len(before["shards"]) + 1


# ---------------------------------------------------------------------------
# end to end: fed_train on the offline FEMNIST mirror
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fed_train_femnist_offline_mirror_end_to_end(tmp_path):
    """Acceptance: `fed_train --dataset femnist --data-dir <cache>` runs
    on the offline mirror with writer-natural partitioning, and a rerun
    against the now pre-existing LEAF files is bit-identical."""
    from repro.launch import fed_train
    argv = ["--dataset", "femnist", "--data-dir", str(tmp_path),
            "--rounds", "2", "--clients", "4", "--clauses", "8",
            "--local-epochs", "1", "--sampling", "weighted",
            "--participation", "0.5"]
    first = fed_train.main(argv)
    second = fed_train.main(argv)        # parses pre-existing files
    assert first == second
    assert len(first["acc_per_round"]) == 2
    assert first["upload_bytes"] > 0


def test_fed_train_synthfemnist_mirror_is_writer_natural(tmp_path):
    """The LEAF flavours route through the natural partitioner: the
    partition sizes driving weighted sampling are the real
    heterogeneous per-writer counts."""
    from repro.data.ingest import registry as datasets
    from repro.data.ingest import natural as nat
    pool = datasets.load("synthfemnist", tmp_path, side=8, n_samples=400,
                         seed=0, n_writers=10)
    cd = nat.partition_writers(pool, n_clients=5, n_train=8, n_test=4,
                               n_conf=4, key=jax.random.PRNGKey(1))
    sizes = np.asarray(cd.sizes)
    assert len(set(sizes.tolist())) > 1


# ---------------------------------------------------------------------------
# fetch-and-verify (offline: mirror files + file:// URLs, no network)
# ---------------------------------------------------------------------------

def test_fetch_place_verifies_then_lands_in_registry_cache(tmp_path):
    """verify→place drops a file into exactly the layout the registry
    reads, with the .sha256 sidecar idx.read checks — exercised against
    an offline-mirror-written archive standing in for a real download."""
    from repro.data.ingest import fetch, mirror
    from repro.data.ingest import registry as datasets
    staging = tmp_path / "staging"
    mirror.write_idx_mirror(staging, "synthmnist", 60, 8, 0)
    for f in staging.glob("*.sha256"):
        f.unlink()                      # a raw download has no sidecar
    cache = tmp_path / "cache"
    for name in (mirror.IMAGES_FILE, mirror.LABELS_FILE):
        src = staging / name
        digest = fetch.sha256_path(src)
        dest = fetch.place(src, cache, "synthmnist", name, expect=digest)
        assert dest.exists() and idx.checksum_path(dest).exists()
    pool = datasets.load("synthmnist", cache, side=8, n_samples=60, seed=0)
    assert int(pool.x.shape[0]) == 60


def test_fetch_wrong_digest_places_nothing(tmp_path):
    from repro.data.ingest import fetch, mirror
    staging = tmp_path / "staging"
    mirror.write_idx_mirror(staging, "synthmnist", 40, 8, 0)
    src = staging / mirror.IMAGES_FILE
    cache = tmp_path / "cache"
    with pytest.raises(fetch.FetchError, match="sha256 mismatch"):
        fetch.place(src, cache, "synthmnist", mirror.IMAGES_FILE,
                    expect="0" * 64)
    assert not (cache / "synthmnist").exists()
    assert src.exists()                 # the suspect file stays put


def test_fetch_refuses_to_overwrite_cache_files(tmp_path):
    from repro.data.ingest import fetch, mirror
    mirror.write_idx_mirror(tmp_path / "mnist", "synthmnist", 40, 8, 0)
    staging = tmp_path / "staging"
    mirror.write_idx_mirror(staging, "synthmnist", 40, 8, 1)
    src = staging / mirror.IMAGES_FILE
    with pytest.raises(fetch.FetchError, match="refusing to overwrite"):
        fetch.place(src, tmp_path, "mnist", mirror.IMAGES_FILE,
                    expect=fetch.sha256_path(src))


def test_fetch_downloads_via_file_urls_offline(tmp_path, monkeypatch):
    """The full fetch path — download, pinned-digest verify, place —
    without a socket: file:// URL overrides point at mirror-written
    archives whose digests are pinned for the test."""
    from repro.data.ingest import fetch, mirror
    staging = tmp_path / "staging"
    mirror.write_idx_mirror(staging, "synthmnist", 40, 8, 0)
    urls, digests = {}, {}
    for f in sorted(staging.glob("*.gz")):
        urls[f.name] = f.as_uri()
        digests[f.name] = fetch.sha256_path(f)
    monkeypatch.setitem(fetch.ARCHIVES, "mnist", digests)
    cache = tmp_path / "cache"
    placed = fetch.fetch("mnist", cache, urls=urls)
    assert sorted(p.name for p in placed) == sorted(digests)
    for p in placed:
        assert p.parent == cache / "mnist"
        fetch.verify_file(p, digests[p.name])
    # resumable: a second call is a no-op, not an overwrite error
    assert fetch.fetch("mnist", cache, urls=urls) == []


def test_fetch_unknown_dataset_lists_choices(tmp_path):
    from repro.data.ingest import fetch
    with pytest.raises(ValueError, match="femnist"):
        fetch.fetch("femnist", tmp_path)


def test_fetch_rejects_mirror_standins_masquerading_as_real(tmp_path):
    """Resume must re-verify: an offline-mirror stand-in sitting under
    the real archive's cache name is never silently accepted as the
    pinned real archive."""
    from repro.data.ingest import fetch, mirror
    mirror.write_idx_mirror(tmp_path / "mnist", "synthmnist", 40, 8, 0)
    with pytest.raises(fetch.FetchError, match="stand-in"):
        fetch.fetch("mnist", tmp_path, urls={})
