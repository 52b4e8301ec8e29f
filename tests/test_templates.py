"""Template engine tests — hydration semantics from `miner/src/models.ts:145-220`."""
import pytest

from arbius_tpu.templates import (
    FilterResult,
    HydrationError,
    MiningFilter,
    Template,
    check_model_filter,
    hydrate_input,
    load_template,
    template_names,
)


def test_all_reference_templates_parse():
    names = template_names()
    assert names == sorted(
        ["anythingv3", "kandinsky2", "zeroscopev2xl", "damo",
         "robust_video_matting", "textgen", "trinity", "deepseek_v32",
         "joyai_llm_flash", "dots3_note", "nemotron_h"])
    for n in names:
        t = load_template(n)
        assert t.title
        assert t.outputs


def test_anythingv3_schema():
    t = load_template("anythingv3")
    byname = {f.variable: f for f in t.inputs}
    assert byname["scheduler"].choices == (
        "DDIM", "K_EULER", "DPMSolverMultistep", "K_EULER_ANCESTRAL", "PNDM", "KLMS")
    assert byname["width"].default == 768
    assert byname["num_inference_steps"].max == 500
    assert t.outputs[0].filename == "out-1.png"


class TestHydration:
    @pytest.fixture()
    def t(self):
        return load_template("anythingv3")

    def test_defaults_filled(self, t):
        out = hydrate_input({"prompt": "cat", "negative_prompt": ""}, t)
        assert out["width"] == 768
        assert out["height"] == 768
        assert out["num_inference_steps"] == 20
        assert out["guidance_scale"] == 12
        assert out["scheduler"] == "DPMSolverMultistep"

    def test_missing_required(self, t):
        with pytest.raises(HydrationError, match="missing required field \\(prompt\\)"):
            hydrate_input({"negative_prompt": ""}, t)

    def test_wrong_type_string(self, t):
        with pytest.raises(HydrationError, match="wrong type"):
            hydrate_input({"prompt": 5, "negative_prompt": ""}, t)

    def test_int_rejects_float_and_bool(self, t):
        with pytest.raises(HydrationError, match="wrong type"):
            hydrate_input({"prompt": "x", "negative_prompt": "", "num_inference_steps": 20.5}, t)
        with pytest.raises(HydrationError, match="wrong type"):
            hydrate_input({"prompt": "x", "negative_prompt": "", "num_inference_steps": True}, t)

    def test_decimal_accepts_fraction(self, t):
        # divergence from reference bug models.ts:185-188 (documented)
        out = hydrate_input({"prompt": "x", "negative_prompt": "", "guidance_scale": 17.5}, t)
        assert out["guidance_scale"] == 17.5

    def test_range_enforced_both_ends(self, t):
        # reference bug models.ts:194 never enforced max; we do
        with pytest.raises(HydrationError, match="out of bounds"):
            hydrate_input({"prompt": "x", "negative_prompt": "", "num_inference_steps": 501}, t)
        with pytest.raises(HydrationError, match="out of bounds"):
            hydrate_input({"prompt": "x", "negative_prompt": "", "num_inference_steps": 0}, t)

    def test_enum_membership(self, t):
        with pytest.raises(HydrationError, match="not in enum"):
            hydrate_input({"prompt": "x", "negative_prompt": "", "width": 333}, t)
        with pytest.raises(HydrationError, match="not in enum"):
            hydrate_input({"prompt": "x", "negative_prompt": "", "scheduler": "UniPC"}, t)

    def test_extra_fields_dropped(self, t):
        out = hydrate_input({"prompt": "x", "negative_prompt": "", "bogus": 1}, t)
        assert "bogus" not in out

    def test_file_type(self):
        t = load_template("robust_video_matting")
        out = hydrate_input({"input_video": "QmSomeCid"}, t)
        assert out["input_video"] == "QmSomeCid"
        with pytest.raises(HydrationError, match="wrong type"):
            hydrate_input({"input_video": 7}, t)


class TestFilters:
    def setup_method(self):
        self.t = load_template("kandinsky2")
        self.base = dict(now=1000.0, fee=100, blocktime=0.0, owner="0x" + "aa" * 20)

    def test_unknown_model(self):
        r = check_model_filter({}, model="0x01", **self.base)
        assert r == FilterResult(False, False, None)

    def test_empty_filters_never_pass(self):
        # reference semantics: default__filters = [] -> filterPassed false
        r = check_model_filter({"0x01": (self.t, [])}, model="0x01", **self.base)
        assert r.model_enabled and not r.filter_passed

    def test_allow_all_filter(self):
        r = check_model_filter({"0x01": (self.t, [MiningFilter()])}, model="0x01", **self.base)
        assert r.filter_passed and r.template is self.t

    def test_minfee(self):
        f = [MiningFilter(minfee=101)]
        assert not check_model_filter({"0x01": (self.t, f)}, model="0x01", **self.base).filter_passed
        f = [MiningFilter(minfee=100)]
        assert check_model_filter({"0x01": (self.t, f)}, model="0x01", **self.base).filter_passed

    def test_mintime(self):
        f = [MiningFilter(mintime=2000)]
        assert not check_model_filter({"0x01": (self.t, f)}, model="0x01", **self.base).filter_passed
        f = [MiningFilter(mintime=500)]
        assert check_model_filter({"0x01": (self.t, f)}, model="0x01", **self.base).filter_passed

    def test_owner_restriction(self):
        f = [MiningFilter(owner="0x" + "bb" * 20)]
        assert not check_model_filter({"0x01": (self.t, f)}, model="0x01", **self.base).filter_passed
        f = [MiningFilter(owner=self.base["owner"])]
        assert check_model_filter({"0x01": (self.t, f)}, model="0x01", **self.base).filter_passed

    def test_first_matching_filter_wins(self):
        f = [MiningFilter(minfee=10**18), MiningFilter()]
        assert check_model_filter({"0x01": (self.t, f)}, model="0x01", **self.base).filter_passed


def test_template_rejects_unknown_types():
    with pytest.raises(ValueError, match="unknown input type"):
        Template.from_dict({"meta": {}, "input": [
            {"variable": "x", "type": "blob"}], "output": []})
    with pytest.raises(ValueError, match="unknown output type"):
        Template.from_dict({"meta": {}, "input": [], "output": [
            {"filename": "f", "type": "hologram"}]})
