"""The decode reference's table form: membership looked up at ``h mod
2^log2_m`` in a table filled by the direct test agrees bit for bit with the
direct test, on random full-width keys, at the cell's widths and a small
one."""
import numpy as np
import pytest

from bench.references import decode_plane as ref

U32 = np.uint32


@pytest.mark.parametrize("log2_m,k,rows", [(14, 2, 32), (20, 4, 0), (5, 3, 4),
                                           (5, 1, 0)])
def test_table_form_equals_direct_form(log2_m, k, rows):
    rng = np.random.default_rng([log2_m, k, rows])
    words = (1 << log2_m) // 32 or 1
    per_row = rows > 0
    filt = rng.integers(0, 1 << 32, size=(rows, words) if per_row else words,
                        dtype=U32)
    prefix = rng.integers(0, 1 << 32, size=rows or 8, dtype=U32)
    h1 = rng.integers(0, 1 << 32, size=4096, dtype=U32)
    table = ref.present_table(filt, k, log2_m, per_row)
    assert table.shape == ((rows,) if per_row else ()) + (1 << log2_m,)
    direct = ref.Plane._present(prefix[:, None] ^ h1[None, :], filt, k,
                                log2_m, per_row)
    got = ref.lookup(table, prefix, h1, (1 << log2_m) - 1)
    assert got.dtype == bool and np.array_equal(got, direct)
    assert 0 < direct.sum() < direct.size


def test_reference_run_reports_no_table_mismatch():
    cfg = {"vocab_size": 512,
           "decode_plane": {"n": 4, "L": 32, "log2_m": 9, "k": 2,
                            "canary_log2_m": 12, "canary_k": 4}}
    params = ref.draw_params(cfg, 7)
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, 512, (6, 40))
    logits = rng.standard_normal((3, 6, 512)).astype(np.float32)
    out = ref.run(cfg, params, prompts, lambda t: logits[t % 3], 5)
    assert out["table_mismatches"] == 0
    assert out["tokens"].shape == (5, 6)
    assert ref.compare(out, out) == {"token_mismatches": 0,
                                     "carry_mismatch_rows": 0,
                                     "table_mismatches": 0}
