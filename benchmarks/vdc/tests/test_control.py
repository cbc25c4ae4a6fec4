"""The control (the reference in the program's place, its ARIMA bank in
bfloat16) must come out not correct.  At the rehearsal's tiny size the
bank makes too few forecasts to tell, so this runs at the cell's own size
over its first stream window (32,768 requests): on the OOI cell as it is,
and on the GAGE cell under ``md2``, the paper's mining baseline, whose
reference forecasts on the same bank."""
import control


def test_bfloat16_control_fails():
    for seed in (1, 2, 3):
        row = control.control("ooi_vdc_128g.paper", seed, windows=1)
        assert not row["correct"], row
        assert row["checks"]["ops_differ"]["value"] > 0


def test_md2_bfloat16_control_fails():
    for seed in (1, 2, 3):
        row = control.control("gage_vdc_32g.paper", seed, windows=1,
                              strategy="md2")
        assert not row["correct"], row
        assert row["checks"]["ops_differ"]["value"] > 0


def test_float32_reference_agrees_with_itself():
    row = control.control("ooi_vdc_128g.paper", 4, windows=1,
                          dtype="float32")
    assert row["correct"], row
