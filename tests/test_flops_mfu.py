"""MFU accounting (common/flops.py): FLOP counts, the decode roofline,
and the peak tables — CPU has no peak, an unlisted TPU is an error."""

import pytest

from marian_tpu.common.flops import (hbm_bandwidth, peak_bf16_flops,
                                     transformer_train_flops)


class TestPeakTable:
    def test_known_generations(self):
        assert peak_bf16_flops("TPU v4") == 275e12
        assert peak_bf16_flops("TPU v5 lite") == 197e12
        assert peak_bf16_flops("TPU v5p") == 459e12
        assert peak_bf16_flops("TPU v6 lite") == 918e12
        # v2/v3: jax lists each of the chip's 2 TensorCores as a device,
        # so the table carries PER-DEVICE peaks (half the per-chip number)
        assert peak_bf16_flops("TPU v3") == 61.5e12
        assert peak_bf16_flops("TPU v2") == 22.5e12

    def test_v4_lite_not_confused_with_v4(self):
        assert peak_bf16_flops("TPU v4 lite") == 138e12

    def test_no_tpu_has_no_peak(self):
        assert peak_bf16_flops("cpu") is None
        assert peak_bf16_flops("") is None
        assert hbm_bandwidth("cpu") is None

    @pytest.mark.parametrize("lookup", [peak_bf16_flops, hbm_bandwidth])
    def test_unlisted_tpu_is_an_error_not_a_default(self, lookup):
        with pytest.raises(ValueError, match="TPU v99"):
            lookup("TPU v99")

    def test_roofline_report_refuses_a_device_without_peaks(self):
        from marian_tpu.common.flops import decode_lever_report
        with pytest.raises(ValueError, match="needs a TPU kind"):
            decode_lever_report(1024, 4096, 6, 32000, 16, 24, 256,
                                device_kind="cpu")

    def test_perf_geometry_unlisted_tpu_raises_cpu_reads_zero(self):
        from marian_tpu.obs.perf import PerfMeter
        from marian_tpu.serving import metrics as msm
        meter = PerfMeter()
        meter.enable(msm.Registry())
        dims = dict(emb=64, ffn=128, enc_depth=1, dec_depth=1, vocab=100)
        meter.set_geometry(device_kind="cpu", n_devices=1, **dims)
        assert meter.m_peak.value == 0.0
        meter.set_geometry(device_kind="TPU v5 lite", n_devices=4, **dims)
        assert meter.m_peak.value == 4 * 197e12
        with pytest.raises(ValueError, match="TPU v99"):
            meter.set_geometry(device_kind="TPU v99", n_devices=1, **dims)


class TestTrainFlops:
    dims = dict(emb=512, ffn=2048, enc_depth=6, dec_depth=6, vocab=32000)

    def _f(self, **kw):
        a = dict(self.dims, src_tokens=1000, trg_tokens=1000,
                 src_width=64, trg_width=64)
        a.update(kw)
        return transformer_train_flops(**a)

    def test_magnitude_vs_6n_rule(self):
        """The 6·N·tokens rule of thumb (N = matmul params incl. the tied
        output projection) should agree within ~25% at short widths where
        attention-score terms are small."""
        d, f, L, V = 512, 2048, 6, 32000
        n_enc = L * (4 * d * d + 2 * d * f)
        n_dec = L * (8 * d * d + 2 * d * f)
        n_out = d * V
        approx = 6 * (1000 * n_enc + 1000 * (n_dec + n_out))
        exact = self._f()
        assert 0.75 < exact / approx < 1.25

    def test_attention_term_scales_with_width(self):
        """Same token counts, wider padding → more score FLOPs (each real
        token attends over the padded row). At 32k vocab the logits term
        dominates, so 64→512 widths add ~13%, not 8× — the check is that
        the attention term exists and is the right order, not that it
        dominates."""
        assert self._f(src_width=512, trg_width=512) > 1.10 * self._f()
        # with a small vocab the width term is clearly visible
        small = dict(vocab=1000)
        assert self._f(src_width=512, trg_width=512, **small) \
            > 1.15 * self._f(**small)

    def test_linear_in_tokens(self):
        one = self._f()
        two = self._f(src_tokens=2000, trg_tokens=2000)
        assert abs(two / one - 2.0) < 1e-6

    def test_deeper_costs_more(self):
        assert self._f(enc_depth=12) > self._f() > self._f(enc_depth=3)


class TestDecodeRoofline:
    """VERDICT r3 #5: the decode levers (int8, shortlist) proven on the
    analytic roofline — docs/DECODE_ROOFLINE.md records the defaults
    decision these pins guard."""
    ARGS = dict(emb=1024, ffn=4096, dec_depth=6, vocab=32000,
                t_past=16, src_width=24)

    def _cost(self, rows, **kw):
        from marian_tpu.common.flops import decode_step_cost
        return decode_step_cost(rows=rows, **{**self.ARGS, **kw})

    def test_weight_bytes_do_not_scale_with_rows(self):
        assert self._cost(1)["weight_bytes"] == \
            self._cost(4096)["weight_bytes"]
        assert self._cost(4096)["flops"] > 1000 * self._cost(1)["flops"]

    def test_int8_halves_weight_bytes(self):
        assert self._cost(8, weight_bytes=1.0)["weight_bytes"] * 2 == \
            self._cost(8, weight_bytes=2.0)["weight_bytes"]

    def test_shortlist_cuts_logits_stream(self):
        full = self._cost(8)
        sl = self._cost(8, shortlist=256)
        # V=32k, d=1024 logits table is ~25% of the per-step bytes
        saved = full["weight_bytes"] - sl["weight_bytes"]
        assert saved == (32000 - 256) * 1024 * 2.0

    def test_levers_pay_when_weight_bound_and_fade_at_the_ridge(self):
        from marian_tpu.common.flops import decode_lever_report
        r = decode_lever_report(1024, 4096, 6, 32000, 16, 24, 256,
                                "TPU v4")
        small, big = r["rows"][8], r["rows"][4096]
        assert small["memory_bound"] and not big["memory_bound"]
        assert small["int8_speedup"] > 1.8
        assert small["int8_shortlist_speedup"] > 2.3
        assert abs(big["int8_speedup"] - 1.0) < 1e-6   # compute-bound
        assert big["shortlist_speedup"] > 1.2          # still cuts FLOPs

    def test_defaults_hint_fires_only_when_a_lever_pays(self):
        from marian_tpu.common.flops import decode_defaults_hint
        kw = dict(emb=1024, ffn=4096, dec_depth=6, vocab=32000, rows=64,
                  device_kind="TPU v4")
        hint = decode_defaults_hint(int8_on=False, shortlist_on=False, **kw)
        assert hint and "int8" in hint and "shortlist" in hint
        assert decode_defaults_hint(int8_on=True, shortlist_on=True,
                                    **kw) is None
        # unknown device / CPU: never advise
        assert decode_defaults_hint(int8_on=False, shortlist_on=False,
                                    **{**kw, "device_kind": "cpu"}) is None
        # compute-bound (huge rows): int8 off is fine; shortlist-only
        # advice may fire through its FLOPs cut, int8 must not be forced
        h = decode_defaults_hint(int8_on=True, shortlist_on=False,
                                 **{**kw, "rows": 8192})
        assert h is None or "int8" not in h
