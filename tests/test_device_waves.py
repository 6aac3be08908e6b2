"""The exact scan of a device partition in leaf waves, the pool on the
device, against the host leaf loop that mmap and tiered partitions
take: bit-identical distances and the same ids on the same rows, one
device read a wave, and the engagement counters.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import summarization as S
from repro.core import tree as T
from repro.core.metrics import IOStats
from repro.obs import get_registry
from repro.query import Partition, exact_knn
from repro.query import executor as E
from repro.storage import Segment
from repro.storage.tiers import TieredLeafStore

CFG = S.SummaryConfig(series_len=64, segments=8, bits=4)
LEAF = 64
N = 3001                      # not a multiple of LEAF: a short last leaf
TIED = 12                     # copies of one row: exact distance ties


def _walks(n, seed):
    rng = np.random.default_rng(seed)
    x = np.cumsum(rng.standard_normal((n, CFG.series_len)), axis=1)
    return np.array(S.znormalize(jnp.asarray(x, jnp.float32)))


@pytest.fixture(scope="module")
def data():
    raw = _walks(N, 0)
    rng = np.random.default_rng(1)
    # one row copied across the collection: its copies tie at every
    # distance, more of them than the largest k
    raw[rng.choice(np.arange(1, N), TIED, replace=False)] = raw[0]
    noise = rng.standard_normal((16, CFG.series_len)).astype(np.float32)
    members = rng.choice(N, 15, replace=False)
    queries = np.concatenate([raw[:1], raw[members]]) + 0.2 * noise
    return raw, queries.astype(np.float32)


@pytest.fixture(scope="module")
def tree(data):
    raw, _ = data
    return T.build(jnp.asarray(raw), CFG, leaf_size=LEAF,
                   timestamps=jnp.arange(N, dtype=jnp.int32))


@pytest.fixture(scope="module")
def lazy_tree(data):
    """The same rows, not copied into the tree: verified rows are read
    from the caller's array through the tree's offsets."""
    raw, _ = data
    return T.build(jnp.asarray(raw), CFG, leaf_size=LEAF,
                   materialized=False,
                   timestamps=jnp.arange(N, dtype=jnp.int32))


@pytest.fixture(scope="module")
def segment(tree, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("seg") / "t.coco")
    T.save(tree, path)
    seg = Segment.open(path)
    yield seg
    seg.close()


def _brute_kth(raw, queries, k):
    d = ((queries[:, None, :] - raw[None]) ** 2).sum(-1)
    return np.sort(d, axis=1)[:, k - 1]


@pytest.mark.parametrize("variant", ["plain", "ts_min", "bsf", "small_waves",
                                     "unmaterialized"])
@pytest.mark.parametrize("k", [1, 10])
@pytest.mark.parametrize("nq", [1, 3, 16])
def test_waves_match_host_loop(data, tree, lazy_tree, segment, monkeypatch,
                               nq, k, variant):
    raw, queries = data
    if variant == "unmaterialized":
        tree = lazy_tree
    q = queries[:nq]
    kw = {"k": k}
    if variant == "ts_min":
        kw["ts_min"] = 1000
    elif variant == "bsf":
        # an external bound that prunes rows the pool alone would keep
        kth = _brute_kth(raw, q, k)
        kw["bsf"] = np.where(np.arange(nq) % 2 == 0, 1.5 * kth,
                             np.inf).astype(np.float32)
    elif variant == "small_waves":
        monkeypatch.setattr(E, "_WAVE_BYTES", 1 << 15)
    d_dev, i_dev, st_dev = exact_knn([Partition.from_tree(tree)], q, CFG,
                                     **kw)
    d_host, i_host, st_host = exact_knn([Partition.from_segment(segment)],
                                        q, CFG, **kw)
    np.testing.assert_array_equal(d_dev, d_host)      # bit-identical
    np.testing.assert_array_equal(i_dev, i_host)
    assert st_dev.device_scans == 1 and st_host.device_scans == 0
    assert st_host.device_waves == 0
    n_leaves = -(-N // LEAF)
    assert st_dev.leaves_scanned + st_dev.leaves_pruned == n_leaves
    assert st_dev.leaves_touched <= st_dev.leaves_scanned
    assert st_dev.candidates <= int(st_dev.candidates_per_query.sum())
    if nq == 1:
        assert int(st_dev.candidates_per_query[0]) == st_dev.candidates
        assert int(st_dev.leaves_per_query[0]) == st_dev.leaves_touched
    if variant == "small_waves":
        width, _ = E._wave_shape(1 << (nq - 1).bit_length(),
                                 Partition.from_tree(tree))
        assert st_dev.device_waves > 1
        assert st_dev.device_waves <= -(-st_host.leaves_scanned // width)
    if variant == "plain" and k == 10:
        # the first query's answers are copies of one row, all tied
        assert d_dev[0, 0] == d_dev[0, -1]


def test_stop_test_skips_the_rest(monkeypatch, tmp_path):
    """Small waves over queries whose bound tightens as the scan goes:
    the stop flag ends the scan early, the skipped leaves count as
    pruned, and the answer is still the host loop's."""
    raw = _walks(8000, 0)
    tree = T.build(jnp.asarray(raw), CFG, leaf_size=32)
    T.save(tree, str(tmp_path / "t.coco"))
    seg = Segment.open(str(tmp_path / "t.coco"))
    rng = np.random.default_rng(7)
    q = (raw[[200, 5000]] + 0.3 * rng.standard_normal(
        (2, CFG.series_len))).astype(np.float32)
    monkeypatch.setattr(E, "_WAVE_BYTES", 1 << 14)
    width, _ = E._wave_shape(2, Partition.from_tree(tree))
    try:
        d_dev, i_dev, st_dev = exact_knn([Partition.from_tree(tree)], q,
                                         CFG, k=1)
        d_host, i_host, st_host = exact_knn([Partition.from_segment(seg)],
                                            q, CFG, k=1)
    finally:
        seg.close()
    np.testing.assert_array_equal(d_dev, d_host)
    np.testing.assert_array_equal(i_dev, i_host)
    assert st_dev.leaves_scanned < st_host.leaves_scanned
    assert (st_dev.leaves_scanned + st_dev.leaves_pruned
            == st_host.leaves_scanned + st_host.leaves_pruned)
    assert st_dev.device_waves < -(-st_host.leaves_scanned // width)


def _fixed_reads(st):
    """Device reads of a scan that are not the waves' counts."""
    return st.device_syncs - st.device_waves


@pytest.mark.parametrize("small", [False, True], ids=["one_wave", "waves"])
def test_device_reads_follow_waves_not_leaves(monkeypatch, small):
    """One partition's device reads are its waves plus a constant (the
    query PAA, the planner's, the seed's and the result's), the same
    for a partition four times the size when the wave count is fixed."""
    if small:
        monkeypatch.setattr(E, "_WAVE_BYTES", 1 << 15)
    fixed, waves = set(), set()
    for n_leaves in (16, 64):
        raw = _walks(n_leaves * LEAF - 5, n_leaves)
        tree = T.build(jnp.asarray(raw), CFG, leaf_size=LEAF)
        q = raw[:3] + np.float32(0.5)
        T.exact_search_batch(tree, q, k=5)     # the planner's fence reads
        _, _, st = T.exact_search_batch(tree, q, k=5)
        assert st.device_scans == 1 and st.device_waves >= 1
        assert st.device_syncs <= st.device_waves + 8
        fixed.add(_fixed_reads(st))
        waves.add(st.device_waves)
    assert len(fixed) == 1
    if not small:
        assert waves == {1}          # each partition fits one wave


def test_device_scans_count_device_partitions_only(tree, segment,
                                                   data, monkeypatch):
    raw, queries = data
    reg = get_registry()
    reg.reset()
    _, _, st = T.exact_search_batch(tree, queries[:4], k=3)
    snap = reg.snapshot()
    assert snap["query.device_scans_total"] == 1
    assert snap["query.scan_waves_total"] == st.device_waves >= 1

    def no_waves(*a, **kw):
        raise AssertionError("the wave path scanned a host partition")
    monkeypatch.setattr(E, "_scan_waves", no_waves)
    reg.reset()
    tiers = TieredLeafStore(8 << 20)
    for part in (Partition.from_segment(segment),
                 Partition.from_segment(segment, tiers=tiers)):
        io = IOStats(64)
        _, _, st = exact_knn([part], queries[:4], CFG, k=3, io=io)
        assert st.device_scans == 0 and st.device_waves == 0
        # the host loop charged every scanned leaf's code rows and every
        # verified raw row as it read them
        assert io.counters["seq_read_blocks"] > 0
        assert io.bytes_read + getattr(tiers, "bytes_saved", 0) >= (
            (st.leaves_scanned - 1) * LEAF * CFG.segments
            + st.candidates * CFG.series_len * 4)
    assert reg.snapshot().get("query.device_scans_total", 0) == 0
    assert reg.snapshot().get("query.scan_waves_total", 0) == 0


def test_programs_do_not_depend_on_pruning(tree, data, monkeypatch):
    """Two probes whose fence bounds leave different numbers of leaves,
    and so different numbers of waves, run the same compiled wave
    programs: nothing compiles for a probe after the first."""
    raw, _ = data
    monkeypatch.setattr(E, "_WAVE_BYTES", 1 << 15)
    scan, visited = E._scan_waves, []

    def spy(entry, queries_j, q_paas_j, order, *a):
        visited.append(len(order))
        return scan(entry, queries_j, q_paas_j, order, *a)
    monkeypatch.setattr(E, "_scan_waves", spy)
    rng = np.random.default_rng(3)
    near = raw[[5, 900, 2000]] + np.float32(0.01)  # tight: most leaves go
    far = rng.standard_normal((3, CFG.series_len)).astype(np.float32)
    programs = (E.wave_bound, E.wave_verify, E._wave_result)
    T.exact_search_batch(tree, far, k=1)
    sizes = [p._cache_size() for p in programs]
    T.exact_search_batch(tree, near, k=1)
    width, _ = E._wave_shape(4, Partition.from_tree(tree))
    assert -(-visited[0] // width) != -(-visited[1] // width)
    assert [p._cache_size() for p in programs] == sizes
