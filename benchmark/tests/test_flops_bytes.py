"""The operations-and-bytes functions against counts made by hand."""

import json
import os

from benchmark import flops_bytes

HERE = os.path.dirname(os.path.abspath(__file__))


def test_dense_lr_step_hand_count():
    # batch 4 rows of width 3, float32: features 4*3*4 = 48 B once,
    # labels + weights 2*4*4 = 32 B, coefficient read + gradient write +
    # coefficient write 3*3*4 = 36 B; two products of 2*4*3 flops.
    c = flops_bytes.dense_lr_step(batch=4, dim=3)
    assert c == {"flops": 48.0, "bytes": 116.0}


def test_dense_lr_step_at_the_cell_size():
    c = flops_bytes.dense_lr_step(batch=262_144, dim=123)
    assert c["bytes"] == 262_144 * 123 * 4 + 2 * 262_144 * 4 + 3 * 123 * 4
    assert c["flops"] / c["bytes"] < 1.0  # bound by bytes on any chip


def test_chain_hand_count():
    # 2 rows of width 5: in 2*5*4 = 40 B, out (1 + 2) * 2 * 4 = 24 B.
    c = flops_bytes.chain(rows=2, dim=5)
    assert c == {"flops": 100.0, "bytes": 64.0}


def test_least_seconds_names_the_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops_bytes.least_seconds({"flops": 50.0, "bytes": 20.0}, peaks) == (2.0, "bytes")
    assert flops_bytes.least_seconds({"flops": 500.0, "bytes": 20.0}, peaks) == (5.0, "flops")


def test_peaks_table_has_the_v5e_with_its_source():
    with open(os.path.join(HERE, "..", "peaks.json")) as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    row = peaks["devices"]["TPU v5 lite"]
    assert row == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}
