"""Native host-runtime tests (C++ ctypes lib + fallbacks).
≡ the reference's apex_C flatten/unflatten and multi_tensor_apply
metadata behavior."""

import numpy as np
import pytest

from apex_tpu import csrc


def test_native_lib_builds():
    assert csrc.available(), "host runtime .so failed to build"


def test_flat_layout():
    offsets, total = csrc.flat_layout([100, 50, 128], align=128)
    np.testing.assert_array_equal(offsets, [0, 128, 256])
    assert total == 384
    offsets2, total2 = csrc.flat_layout([100, 50, 128], align=1)
    np.testing.assert_array_equal(offsets2, [0, 100, 150])
    assert total2 == 278


def test_chunk_plan():
    plan = csrc.chunk_plan([5, 12], chunk_size=5)
    expect = [(0, 0, 5), (1, 0, 5), (1, 5, 5), (1, 10, 2)]
    np.testing.assert_array_equal(plan, expect)


def test_shuffle_deterministic_permutation():
    a = csrc.shuffle_indices(1000, seed=42)
    b = csrc.shuffle_indices(1000, seed=42)
    c = csrc.shuffle_indices(1000, seed=43)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert sorted(a.tolist()) == list(range(1000))


def test_gather_rows():
    ds = np.arange(40, dtype=np.float32).reshape(10, 4)
    idx = [3, 0, 7, 7]
    out = csrc.gather_rows(ds, idx)
    np.testing.assert_array_equal(out, ds[idx])

    ds_i = np.arange(30, dtype=np.int32).reshape(10, 3)
    out_i = csrc.gather_rows(ds_i, [9, 1])
    np.testing.assert_array_equal(out_i, ds_i[[9, 1]])


@pytest.mark.parametrize("n,seed", [(0, 3), (1, 7), (1000, 42),
                                    (257, 2 ** 40 + 5)])
def test_shuffle_fallback_is_the_same_permutation(n, seed):
    """The pure-Python path must give the native permutation: an epoch's
    data order may not depend on whether a compiler was present."""
    np.testing.assert_array_equal(csrc._shuffle_indices_py(n, seed),
                                  csrc.shuffle_indices(n, seed))


def test_unavailable_runtime_warns_with_reason(monkeypatch, tmp_path):
    """A failed build or load is reported once, with its reason — never
    a silent switch to the pure-Python paths."""
    monkeypatch.setattr(csrc, "_LIB", None)
    monkeypatch.setattr(csrc, "_TRIED", False)
    bad = tmp_path / "libapex_tpu_host.so"
    bad.write_bytes(b"not an ELF file")
    monkeypatch.setattr(csrc, "_SO", str(bad))
    with pytest.warns(RuntimeWarning, match="native host runtime"):
        assert csrc.available() is False
    np.testing.assert_array_equal(
        csrc.flat_layout([100, 50], align=128)[0], [0, 128])
