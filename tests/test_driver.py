"""End-to-end driver tests on tiny fixtures — the reference's full-driver
integration tests (SURVEY.md §4): train → files exist → metrics pass
thresholds → score round-trip."""

import json
import os

import numpy as np
import pytest

from photon_tpu.data.synthetic import make_glm_data, write_libsvm
from photon_tpu.drivers import score as score_driver
from photon_tpu.drivers import train as train_driver


@pytest.fixture(scope="module")
def libsvm_files(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("libsvm")
    batch, _ = make_glm_data(400, 13, task="logistic_regression", seed=1)
    x = np.asarray(batch.x)[:, :-1]  # drop intercept column; driver re-adds
    y = np.asarray(batch.label)
    train_p, val_p = str(tmp / "train.libsvm"), str(tmp / "val.libsvm")
    write_libsvm(train_p, x[:300], y[:300])
    write_libsvm(val_p, x[300:], y[300:])
    return train_p, val_p


def test_device_policy_refuses_a_platform_nobody_asked_for(monkeypatch):
    """--backend tpu (the default) with no JAX_PLATFORMS=cpu in the
    environment REQUIRES a tpu: finding anything else raises, naming it."""
    from photon_tpu.drivers import common

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match=r"'tpu' was asked for.*'cpu'"):
        common.select_backend("tpu")
    # Asked for by the environment or the flag, the host is a choice.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert common.select_backend("tpu")["platform"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    assert common.select_backend("cpu")["platform"] == "cpu"


def test_train_driver_end_to_end(libsvm_files, tmp_path):
    train_p, val_p = libsvm_files
    out = str(tmp_path / "out")
    summary = train_driver.run(train_driver.build_parser().parse_args([
        "--input", train_p, "--validation-input", val_p,
        "--task", "logistic_regression", "--optimizer", "lbfgs",
        "--reg-type", "l2", "--reg-weights", "0.1,1.0,10.0",
        "--output-dir", out, "--backend", "cpu",
        "--save-all-models", "--variance-computation", "simple",
    ]))
    assert os.path.exists(os.path.join(out, "best_model.avro"))
    assert os.path.exists(os.path.join(out, "feature_index.json"))
    assert os.path.exists(os.path.join(out, "model_lambda_0.1.avro"))
    with open(os.path.join(out, "training_summary.json")) as f:
        persisted = json.load(f)
    assert persisted["best_lambda"] == summary["best_lambda"]
    # Explicit CPU is a choice the summary records, not a fallback.
    assert persisted["device"] == {
        "platform": "cpu", "device_kind": "cpu", "device_count": 8,
    }
    # Model should beat chance comfortably on separable-ish synthetic data.
    aucs = [e["metrics"]["AUC"] for e in summary["sweep"]]
    assert max(aucs) > 0.7
    # Lambda sweep must actually produce different models.
    assert len({e["final_value"] for e in summary["sweep"]}) == 3
    # Per-lambda diagnostic report artifacts (the reference's deprecated
    # diagnostic reports — SURVEY.md §3.2; VERDICT r3 item 8).
    for lam in ("0.1", "1", "10"):
        path = os.path.join(out, "diagnostics", f"report_lambda_{lam}.json")
        assert os.path.exists(path), path
        with open(path) as f:
            report = json.load(f)
        assert report["convergence_trace"], "trace must be recorded"
        assert report["coefficients"]["dim"] == 13  # 12 features + intercept
        assert "AUC" in report["metrics"]
    assert os.path.exists(os.path.join(out, "diagnostics", "report.md"))


def test_train_score_round_trip(libsvm_files, tmp_path):
    train_p, val_p = libsvm_files
    out = str(tmp_path / "out")
    train_driver.run(train_driver.build_parser().parse_args([
        "--input", train_p, "--task", "logistic_regression",
        "--reg-weights", "1.0", "--output-dir", out, "--backend", "cpu",
    ]))
    score_out = str(tmp_path / "scores")
    result = score_driver.run(score_driver.build_parser().parse_args([
        "--input", val_p, "--model", os.path.join(out, "best_model.avro"),
        "--output-dir", score_out, "--backend", "cpu",
        "--evaluators", "AUC,LOGISTIC_LOSS",
    ]))
    assert result["num_scored"] == 100
    assert result["metrics"]["AUC"] > 0.7
    scores = np.loadtxt(os.path.join(score_out, "scores.txt"))
    assert scores.shape == (100,)


def test_train_driver_owlqn_sparsifies(tmp_path):
    out = str(tmp_path / "out")
    summary = train_driver.run(train_driver.build_parser().parse_args([
        "--input", "synthetic:linear_regression:300:10:3",
        "--task", "linear_regression", "--optimizer", "owlqn",
        "--reg-type", "elastic_net", "--reg-weights", "30.0",
        "--output-dir", out, "--backend", "cpu", "--model-format", "json",
    ]))
    with open(os.path.join(out, "best_model.json")) as f:
        record = json.load(f)
    # Sparse storage: OWL-QN must have zeroed some coefficients, and zeros
    # are dropped on save (10 features + intercept, minus exact zeros).
    assert len(record["means"]) < 11
    assert summary["sweep"][0]["convergence_reason"] in (
        "FUNCTION_VALUES_TOLERANCE", "GRADIENT_TOLERANCE", "MAX_ITERATIONS",
        "OBJECTIVE_NOT_IMPROVING",
    )


def test_train_driver_tron_poisson(tmp_path):
    out = str(tmp_path / "out")
    summary = train_driver.run(train_driver.build_parser().parse_args([
        "--input", "synthetic:poisson_regression:300:8:4:77",
        "--validation-input", "synthetic:poisson_regression:300:8:5:77",
        "--task", "poisson_regression", "--optimizer", "tron",
        "--reg-type", "l2", "--reg-weights", "1.0",
        "--output-dir", out, "--backend", "cpu",
    ]))
    # Poisson loss on validation should beat the intercept-only baseline.
    assert summary["sweep"][0]["metrics"]["POISSON_LOSS"] < 2.0


def test_score_no_intercept_model(tmp_path):
    # The score driver must take intercept presence from the index map, not
    # the CLI flag: a model trained with --no-intercept scored with default
    # flags would otherwise shift feature ids (review finding).
    batch, _ = make_glm_data(300, 12, task="logistic_regression", seed=3,
                             intercept=False)
    x, y = np.asarray(batch.x), np.asarray(batch.label)
    train_p = str(tmp_path / "train.libsvm")
    write_libsvm(train_p, x, y)
    out = str(tmp_path / "out")
    train_driver.run(train_driver.build_parser().parse_args([
        "--input", train_p, "--task", "logistic_regression",
        "--reg-weights", "1.0", "--output-dir", out, "--backend", "cpu",
        "--no-intercept",
    ]))
    score_out = str(tmp_path / "scores")
    result = score_driver.run(score_driver.build_parser().parse_args([
        "--input", train_p, "--model", os.path.join(out, "best_model.avro"),
        "--output-dir", score_out, "--backend", "cpu", "--evaluators", "AUC",
    ]))
    # With the flag mistakenly trusted, ids shift and AUC collapses.
    assert result["metrics"]["AUC"] > 0.7


def test_score_rejects_sharded_evaluators_before_scoring(tmp_path):
    batch, _ = make_glm_data(100, 8, task="logistic_regression", seed=4)
    x, y = np.asarray(batch.x)[:, :-1], np.asarray(batch.label)
    train_p = str(tmp_path / "train.libsvm")
    write_libsvm(train_p, x, y)
    out = str(tmp_path / "out")
    train_driver.run(train_driver.build_parser().parse_args([
        "--input", train_p, "--task", "logistic_regression",
        "--reg-weights", "1.0", "--output-dir", out, "--backend", "cpu",
    ]))
    score_out = str(tmp_path / "scores")
    with pytest.raises(ValueError, match="entity ids"):
        score_driver.run(score_driver.build_parser().parse_args([
            "--input", train_p, "--model", os.path.join(out, "best_model.avro"),
            "--output-dir", score_out, "--backend", "cpu",
            "--evaluators", "SHARDED_AUC:user",
        ]))
    # The guard must fire before any scoring output is written.
    assert not os.path.exists(os.path.join(score_out, "scores.txt"))


def test_a1a_fixture_anchor(tmp_path):
    """The committed a1a-statistics fixture is a determinism anchor: a
    regression in loss/optimizer/data plumbing moves its held-out AUC
    (BASELINE.md round-3 table)."""
    from photon_tpu.data.fixtures import a1a_fixture_paths
    from photon_tpu.drivers import train

    train_path, test_path = a1a_fixture_paths()
    summary = train.run(train.build_parser().parse_args([
        "--backend", "cpu",
        "--input", train_path, "--validation-input", test_path,
        "--task", "logistic_regression", "--optimizer", "lbfgs",
        "--reg-type", "l2", "--reg-weights", "1.0",
        "--max-iterations", "100",
        "--output-dir", str(tmp_path / "out"),
    ]))
    auc = summary["sweep"][0]["metrics"]["AUC"]
    assert 0.80 < auc < 0.87, f"a1a fixture AUC anchor moved: {auc}"


@pytest.mark.parametrize("forward", [False, True])
def test_train_driver_pallas_kernel_a1a(tmp_path, monkeypatch, forward):
    """PHOTON_SPARSE_GRAD=pallas trains a1a end-to-end through the
    slab-aligned Mosaic kernel (interpret mode on CPU) and reaches the same
    AUC band as the fm path (VERDICT r3 item 2 'done' criterion).  With
    PHOTON_SPARSE_MARGIN=pallas the margins also route through the
    transposed layout (full fwd+bwd Pallas sparse pipeline)."""
    from photon_tpu.data.fixtures import a1a_fixture_paths

    monkeypatch.setenv("PHOTON_SPARSE_GRAD", "pallas")
    if forward:
        monkeypatch.setenv("PHOTON_SPARSE_MARGIN", "pallas")
    else:
        # An ambient PHOTON_SPARSE_MARGIN would silently collapse both
        # params onto the same path.
        monkeypatch.delenv("PHOTON_SPARSE_MARGIN", raising=False)
    train_path, test_path = a1a_fixture_paths()
    summary = train_driver.run(train_driver.build_parser().parse_args([
        "--backend", "cpu",
        "--input", train_path, "--validation-input", test_path,
        "--task", "logistic_regression", "--optimizer", "lbfgs",
        "--reg-type", "l2", "--reg-weights", "1.0",
        "--max-iterations", "25",
        "--output-dir", str(tmp_path / "out"),
    ]))
    auc = summary["sweep"][0]["metrics"]["AUC"]
    assert 0.80 < auc < 0.87, f"pallas-path a1a AUC out of band: {auc}"


def test_score_stream_matches_whole(libsvm_files, tmp_path):
    """score --stream over part files == whole-set scoring, exactly."""
    train_p, val_p = libsvm_files
    out = str(tmp_path / "model")
    train_driver.run(train_driver.build_parser().parse_args([
        "--input", train_p, "--task", "logistic_regression",
        "--reg-weights", "1.0", "--max-iterations", "30",
        "--output-dir", out, "--backend", "cpu",
    ]))

    # Split the validation file into 3 uneven parts.
    lines = open(val_p).read().splitlines(keepends=True)
    parts = tmp_path / "parts"
    parts.mkdir()
    cuts = [0, 13, 60, len(lines)]
    for pi in range(3):
        with open(parts / f"part-{pi}.libsvm", "w") as f:
            f.writelines(lines[cuts[pi]:cuts[pi + 1]])

    common_args = [
        "--model", os.path.join(out, "best_model.avro"),
        "--backend", "cpu",
        "--evaluators", "AUC",
    ]
    whole = score_driver.run(score_driver.build_parser().parse_args(
        common_args + ["--input", val_p,
                       "--output-dir", str(tmp_path / "w")]))
    streamed = score_driver.run(score_driver.build_parser().parse_args(
        common_args + ["--input", str(parts / "*.libsvm"), "--stream",
                       "--output-dir", str(tmp_path / "s")]))
    assert streamed["streamed"] and streamed["num_scored"] == whole["num_scored"]
    sw = np.loadtxt(tmp_path / "w" / "scores.txt")
    ss = np.loadtxt(tmp_path / "s" / "scores.txt")
    np.testing.assert_array_equal(sw, ss)
    assert streamed["metrics"]["AUC"] == pytest.approx(
        whole["metrics"]["AUC"], rel=1e-9
    )


def test_sweep_warm_start_reduces_iterations(libsvm_files, tmp_path):
    """The regularization path warm start must land on the same optima with
    fewer total iterations than cold starts."""
    train_p, _ = libsvm_files
    totals, finals = {}, {}
    for mode, flag in (("warm", "--sweep-warm-start"),
                       ("cold", "--no-sweep-warm-start")):
        out = str(tmp_path / mode)
        summary = train_driver.run(train_driver.build_parser().parse_args([
            "--input", train_p, "--task", "logistic_regression",
            "--reg-weights", "10,3,1,0.3", "--max-iterations", "200",
            flag, "--output-dir", out, "--backend", "cpu",
        ]))
        totals[mode] = sum(e["iterations"] for e in summary["sweep"])
        finals[mode] = [e["final_value"] for e in summary["sweep"]]
    np.testing.assert_allclose(finals["warm"], finals["cold"], rtol=1e-4)
    assert totals["warm"] < totals["cold"], totals


def test_real_data_dir_hooks(tmp_path, monkeypatch):
    """PHOTON_REAL_DATA_DIR switches fixtures to operator-provided real
    datasets (VERDICT r3 item 9 infrastructure): a1a paths resolve to the
    verbatim files, and MovieLens-1M .dat files parse into the GAME layout
    (label = rating >= 4, genre indicator shards)."""
    from photon_tpu.data.fixtures import a1a_fixture_paths, movielens_dataset

    # Without the env (or with files missing), fixtures back everything.
    monkeypatch.delenv("PHOTON_REAL_DATA_DIR", raising=False)
    tr, te = a1a_fixture_paths()
    assert tr.endswith("a1a.libsvm")
    monkeypatch.setenv("PHOTON_REAL_DATA_DIR", str(tmp_path))
    tr2, _ = a1a_fixture_paths()
    assert tr2.endswith("a1a.libsvm"), "missing real files must fall back"

    # Drop in miniature verbatim-format real files.
    (tmp_path / "a1a").write_text("-1 3:1 11:1\n+1 5:1 77:1\n")
    (tmp_path / "a1a.t").write_text("+1 4:1\n")
    ml = tmp_path / "ml-1m"
    ml.mkdir()
    (ml / "movies.dat").write_text(
        "1::Toy Story (1995)::Animation|Children's|Comedy\n"
        "2::Jumanji (1995)::Adventure|Children's|Fantasy\n",
        encoding="latin-1",
    )
    (ml / "ratings.dat").write_text(
        "1::1::5::978300760\n1::2::3::978302109\n2::1::4::978301968\n",
        encoding="latin-1",
    )

    tr3, te3 = a1a_fixture_paths()
    assert tr3 == str(tmp_path / "a1a") and te3 == str(tmp_path / "a1a.t")

    data, maps = movielens_dataset()
    assert data.num_examples == 3
    np.testing.assert_array_equal(data.label, [1.0, 0.0, 1.0])
    np.testing.assert_array_equal(data.id_columns["userId"], [1, 1, 2])
    x = data.shard("global").x
    assert x.shape == (3, 19)  # 18 genres + intercept
    # Row 0 rates movie 1: Animation + Children's + Comedy set.
    assert x[0].sum() == 4.0 and x[0, -1] == 1.0
    assert maps["per_user"].intercept_id is not None
