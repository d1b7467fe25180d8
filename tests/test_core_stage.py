"""Stage / Pipeline / serialization round-trip tests (reference:
RoundTripTestBase, core/test/base/.../TestBase.scala:179-255)."""

import numpy as np
import pytest

from mmlspark_tpu.core.params import Param
from mmlspark_tpu.core.serialize import load_dataset, save_dataset
from mmlspark_tpu.core.stage import (
    Estimator,
    Model,
    Pipeline,
    PipelineModel,
    PipelineStage,
    Transformer,
)
from mmlspark_tpu.core.schema import ColumnMeta, CategoricalMeta
from mmlspark_tpu.data.dataset import Dataset


class AddConstant(Transformer):
    input_col = Param("input column", "numbers", ptype=str)
    output_col = Param("output column", "plus", ptype=str)
    amount = Param("amount to add", 1.0, ptype=float)

    def _transform(self, ds):
        return ds.with_column(self.output_col, ds[self.input_col] + self.amount)


class MeanCenter(Estimator):
    input_col = Param("input column", "numbers", ptype=str)
    output_col = Param("output column", "centered", ptype=str)

    def _fit(self, ds):
        return MeanCenterModel(
            input_col=self.input_col,
            output_col=self.output_col,
            mean=float(np.mean(ds[self.input_col])),
        )


class MeanCenterModel(Model):
    input_col = Param("input column", "numbers", ptype=str)
    output_col = Param("output column", "centered", ptype=str)
    mean = Param("learned mean", 0.0, ptype=float)

    def _transform(self, ds):
        return ds.with_column(self.output_col, ds[self.input_col] - self.mean)


def test_transformer(basic_dataset):
    out = AddConstant(amount=2.0).transform(basic_dataset)
    assert list(out["plus"]) == [2, 3, 4, 5]


def test_estimator_fit_transform(basic_dataset):
    model = MeanCenter().fit(basic_dataset)
    out = model.transform(basic_dataset)
    assert abs(float(np.mean(out["centered"]))) < 1e-12


def test_pipeline(basic_dataset):
    pipe = Pipeline([AddConstant(amount=10.0), MeanCenter(input_col="plus")])
    model = pipe.fit(basic_dataset)
    assert isinstance(model, PipelineModel)
    out = model.transform(basic_dataset)
    assert "plus" in out and "centered" in out


def test_registry_contains_stages():
    reg = PipelineStage.registry()
    for name in ("AddConstant", "MeanCenter", "MeanCenterModel", "Pipeline"):
        assert name in reg
    # abstract intermediates stay out
    assert "Transformer" not in reg and "Estimator" not in reg


def test_stage_round_trip(tmp_path, basic_dataset):
    stage = AddConstant(amount=3.5)
    stage.save(str(tmp_path / "s"))
    loaded = PipelineStage.load(str(tmp_path / "s"))
    assert type(loaded) is AddConstant
    assert loaded.amount == 3.5
    np.testing.assert_array_equal(
        loaded.transform(basic_dataset)["plus"],
        stage.transform(basic_dataset)["plus"],
    )


def test_fitted_pipeline_round_trip(tmp_path, basic_dataset):
    model = Pipeline([AddConstant(amount=1.0), MeanCenter(input_col="plus")]).fit(
        basic_dataset
    )
    model.save(str(tmp_path / "pm"))
    loaded = PipelineStage.load(str(tmp_path / "pm"))
    a = model.transform(basic_dataset)
    b = loaded.transform(basic_dataset)
    np.testing.assert_allclose(
        np.asarray(a["centered"], float), np.asarray(b["centered"], float)
    )


def test_array_param_round_trip(tmp_path):
    class Weighted(Transformer):
        weights = Param("weight matrix")

        def _transform(self, ds):
            return ds

    w = np.arange(12.0).reshape(3, 4)
    stage = Weighted().set(weights={"layer": {"kernel": w, "bias": np.zeros(4)}})
    stage.save(str(tmp_path / "w"))
    loaded = PipelineStage.load(str(tmp_path / "w"))
    np.testing.assert_array_equal(loaded.weights["layer"]["kernel"], w)


def test_dataset_round_trip(tmp_path, basic_dataset):
    ds = basic_dataset.with_meta(
        "words",
        ColumnMeta(categorical=CategoricalMeta(("a", "b"), has_null=True)),
    ).with_partitions(3)
    save_dataset(ds, str(tmp_path / "d"))
    back = load_dataset(str(tmp_path / "d"))
    assert back.num_rows == 4
    assert list(back["words"]) == list(ds["words"])
    assert back.meta_of("words").categorical.has_null
    assert back.num_partitions == 3
    np.testing.assert_array_equal(back["doubles"], ds["doubles"])


def test_dataset_round_trip_meta_arrays_and_reserved_names(tmp_path):
    ds = Dataset({"file": np.arange(3), "x": np.ones(3)}).with_meta(
        "x", ColumnMeta(extra={"centers": np.zeros(3)})
    )
    save_dataset(ds, str(tmp_path / "d2"))
    back = load_dataset(str(tmp_path / "d2"))
    np.testing.assert_array_equal(back["file"], np.arange(3))
    np.testing.assert_array_equal(back.meta_of("x").extra["centers"], np.zeros(3))


def test_int_param_rejects_fractional_float():
    from mmlspark_tpu.core.exceptions import ParamError
    from mmlspark_tpu.core.params import Param

    class P(Transformer):
        n = Param("count", 1, ptype=int)

        def _transform(self, ds):
            return ds

    with pytest.raises(ParamError):
        P().set(n=2.7)
    assert P().set(n=2.0).n == 2


def test_pipeline_stages_append_not_discarded(basic_dataset):
    p = Pipeline()
    p.stages.append(AddConstant(amount=4.0))
    out = p.fit(basic_dataset).transform(basic_dataset)
    assert list(out["plus"]) == [4, 5, 6, 7]


def test_numpy_scalar_param_accepted():
    stage = AddConstant().set(amount=np.float64(2.5))
    assert stage.amount == 2.5 and isinstance(stage.amount, float)

    class Counted(Transformer):
        n = Param("count", 0, ptype=int)

        def _transform(self, ds):
            return ds

    assert Counted().set(n=np.int64(5)).n == 5


def test_pipeline_skips_transform_after_last_estimator(basic_dataset):
    calls = []

    class Spy(Transformer):
        def _transform(self, ds):
            calls.append("t")
            return ds

    class SpyEst(Estimator):
        def _fit(self, ds):
            return Spy()

    Pipeline([SpyEst(), Spy()]).fit(basic_dataset)
    # neither the fitted model of the last estimator nor the trailing
    # transformer should have run during fit
    assert calls == []


def test_is_tpu_ignores_device_kind(monkeypatch):
    """The platform decides, never ``device_kind``: a device of an
    unknown platform is NOT a TPU whatever it calls itself — it gets
    interpreted kernels, dense attention and smoke scale, visibly,
    instead of TPU paths chosen on a name."""
    from mmlspark_tpu.core import env

    class _Dev:
        def __init__(self, platform, kind):
            self.platform = platform
            self.device_kind = kind

    import jax

    for kind in ("TPU v5 lite", "v6e", "tpu"):
        monkeypatch.setattr(jax, "devices", lambda k=kind: [_Dev("other", k)])
        assert not env.is_tpu()
