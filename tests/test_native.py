"""Native (C++) ingest library vs NumPy reference semantics."""

import numpy as np
import pytest

from tpu_distalg import native


def _random_edges(n, v, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, v, size=(n, 2)).astype(np.int64)


def test_dedupe_matches_numpy_unique():
    edges = _random_edges(50_000, 500)  # guaranteed duplicates
    got = native.dedupe_edges(edges)
    expect = np.unique(edges, axis=0)
    np.testing.assert_array_equal(got, expect)
    assert len(got) < len(edges)


def test_dedupe_large_vertex_ids_general_path():
    """Ids above 2^32 exercise the index-sort path."""
    edges = np.array(
        [[1 << 40, 5], [3, 1 << 35], [1 << 40, 5], [3, 1 << 35], [0, 1]],
        dtype=np.int64,
    )
    got = native.dedupe_edges(edges)
    expect = np.unique(edges, axis=0)
    np.testing.assert_array_equal(got, expect)


def test_out_degree_matches_bincount():
    edges = _random_edges(100_000, 1000, seed=1)
    deg = native.out_degree(edges[:, 0], 1000)
    np.testing.assert_array_equal(
        deg, np.bincount(edges[:, 0], minlength=1000)
    )


def test_csr_offsets():
    src = np.array([0, 0, 1, 3, 3, 3], dtype=np.int64)
    off = native.csr_offsets(src, 5)
    np.testing.assert_array_equal(off, [0, 2, 3, 3, 6, 6])
    # offsets reconstruct per-vertex degree
    np.testing.assert_array_equal(np.diff(off), [2, 1, 0, 3, 0])


def test_parse_edges_text(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text("# comment\n1 2\n3 4\n\n5 6\n")
    got = native.parse_edges_text(str(p), capacity=10)
    np.testing.assert_array_equal(got, [[1, 2], [3, 4], [5, 6]])


def test_parse_edges_capacity_error(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text("1 2\n3 4\n")
    with pytest.raises(ValueError):
        native.parse_edges_text(str(p), capacity=1)


def test_parse_edges_missing_file():
    with pytest.raises(FileNotFoundError):
        native.parse_edges_text("/nonexistent/file.txt", capacity=4)


def test_prepare_edges_uses_native_and_matches(mesh8):
    """End-to-end: pagerank over pre/post-native prepare gives identical
    structure."""
    from tpu_distalg.ops import graph as gops

    edges = _random_edges(20_000, 2_000, seed=3)
    el = gops.prepare_edges(edges)
    expect = np.unique(edges, axis=0)
    np.testing.assert_array_equal(
        np.stack([el.src, el.dst], 1), expect.astype(np.int32)
    )
    assert el.n_vertices == int(edges.max()) + 1


@pytest.mark.skipif(not native.available(), reason="native lib not built")
def test_native_is_loaded():
    assert native.load() is not None


def test_out_degree_rejects_out_of_range_ids():
    """C++ histogram is unchecked; the wrapper must refuse ids >= n."""
    with pytest.raises(ValueError):
        native.out_degree(np.array([0, 1, 500_000], dtype=np.int64), 2)


def test_dedupe_edges_pair_contiguous():
    edges = _random_edges(10_000, 100, seed=4)
    src, dst = native.dedupe_edges_pair(edges)
    assert src.flags["C_CONTIGUOUS"] and dst.flags["C_CONTIGUOUS"]
    expect = np.unique(edges, axis=0)
    np.testing.assert_array_equal(src, expect[:, 0])
    np.testing.assert_array_equal(dst, expect[:, 1])


def test_counting_sort_perm_matches_numpy():
    from tpu_distalg import native

    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1000, size=100_000)
    got = native.counting_sort_perm(keys, 1000)
    want = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(got, want)


def test_counting_sort_perm_rejects_out_of_range():
    """Validation happens Python-side, so it holds with or without the
    native library."""
    from tpu_distalg import native

    with pytest.raises(ValueError, match="out of range"):
        native.counting_sort_perm(np.array([0, 5, 2]), 4)
    with pytest.raises(ValueError, match="out of range"):
        native.counting_sort_perm(np.array([-1, 0]), 4)


def test_loader_ignores_library_not_built_from_this_source(
        tmp_path, monkeypatch, capsys):
    """The binary's name vouches for the committed source + flags: a
    ``.so`` under any other name (a stale build, one made for another
    CPU) is never opened — with no way to build the matching one the
    loader says so once and NumPy takes over."""
    import os
    import shutil

    real = native.lib_path()
    assert real is not None and os.path.basename(real).startswith(
        "libtda_ingest-")
    # a package dir holding only foreign binaries: the pre-hash name
    # and a hash of some other source
    for name in ("libtda_ingest.so", "libtda_ingest-000000000000.so"):
        (tmp_path / name).write_bytes(b"not an ELF file")
    monkeypatch.setattr(native, "_here", str(tmp_path))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_load_attempted", False)
    monkeypatch.setattr(native, "_build", lambda path: "build failed: "
                        "no compiler (test)")
    assert native.load() is None          # neither foreign file opened
    err = capsys.readouterr().err
    assert err.count("[native] graph ingest path: numpy") == 1
    assert "no compiler (test)" in err
    native.load()                          # announced once, not twice
    assert "[native]" not in capsys.readouterr().err
    # the fallback still answers
    np.testing.assert_array_equal(
        native.dedupe_edges(np.array([[1, 2], [1, 2]], np.int64)),
        [[1, 2]])
    # and the name is a function of the source: other bytes, other name
    src = tmp_path / "src"
    shutil.copytree(native._SRC_DIR, src)
    (src / "graph_ingest.cpp").write_text("// changed\n")
    monkeypatch.setattr(native, "_SRC_DIR", str(src))
    assert os.path.basename(native.lib_path()) != os.path.basename(real)
