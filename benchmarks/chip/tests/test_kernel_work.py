"""The roofline function against a band step worked out by hand, and the
peaks table's refusal of chips it does not list."""

import pytest

import kernel_work
from peaks import peak

POLICE = [{"kind": "embed", "width": 128}, {"kind": "embed", "width": 128},
          {"kind": "scalar", "range": 40.0}]


def test_police_band_step_by_hand():
    # 100,000 L rows x 512 R columns; two embeds of 128 + 2 marker dims
    w = kernel_work.band_step_work(100_000, 512, POLICE, [[0], [1, 2]])
    assert w["ops"] == 2 * 100_000 * 512 * 130 * 2 == 26_624_000_000
    mask = 100_000 * 512 // 8                          # 6,400,000
    embeds = 2 * (100_000 + 512) * 130 * 4             # 104,532,480
    scalar = (100_000 + 512) * 4                       # 402,048
    assert w["bytes"] == mask + embeds + scalar == 111_334_528
    t, bound = kernel_work.least_time(w, "TPU v5 lite")
    # 2.6624e10 / 197e12 = 135.15 us < 1.1133e8 / 819e9 = 135.94 us
    assert bound == "memory"
    assert t == pytest.approx(111_334_528 / 819e9)


def test_police_band_step_at_3072_dims_by_hand():
    # the deployment's widths: two embeds of 3072 + 2 marker dims
    wide = [dict(f, width=3072) if f["kind"] == "embed" else f
            for f in POLICE]
    w = kernel_work.band_step_work(100_000, 512, wide, [[0], [1, 2]])
    assert w["ops"] == 2 * 100_000 * 512 * 3074 * 2 == 629_555_200_000
    embeds = 2 * (100_000 + 512) * 3074 * 4            # 2,471,791,104
    assert w["bytes"] == 6_400_000 + embeds + 402_048 == 2_478_593_152
    t, bound = kernel_work.least_time(w, "TPU v5 lite")
    # 6.2956e11 / 197e12 = 3.196 ms > 2.4786e9 / 819e9 = 3.026 ms
    assert bound == "compute"
    assert t == pytest.approx(629_555_200_000 / 197e12)


def test_unused_features_cost_nothing():
    w = kernel_work.band_step_work(4096, 512, POLICE, [[0]])
    assert w["ops"] == 2 * 4096 * 512 * 130
    assert w["bytes"] == 4096 * 512 // 8 + (4096 + 512) * 130 * 4


def test_peaks_by_dtype():
    assert peak("TPU v5 lite", "float32") == (197e12, 819e9)
    assert peak("TPU v5 lite", "int8") == (393e12, 819e9)


@pytest.mark.parametrize("kind,dtype", [("TPU v9", "float32"),
                                        ("cpu", "float32"),
                                        ("TPU v5 lite", "float64")])
def test_missing_peak_is_an_error(kind, dtype):
    with pytest.raises(KeyError):
        peak(kind, dtype)
