"""End-to-end driver tests on generated fixtures.

The analog of the reference's acceptance suites:
- DriverIntegTest (legacy, heart.avro over every task/optimizer combo)
- cli/game/training/DriverTest + cli/game/scoring/DriverTest
"""

import json
import os

import numpy as np
import pytest

from photon_ml_tpu.cli.feature_indexing_job import main as index_main
from photon_ml_tpu.cli.game_scoring_driver import main as score_main
from photon_ml_tpu.cli.game_training_driver import main as game_main
from photon_ml_tpu.cli.legacy_driver import (
    LegacyDriver,
    main as legacy_main,
    parse_args,
)
from photon_ml_tpu.io import schemas
from photon_ml_tpu.io.avro import write_container
from photon_ml_tpu.io.model_io import load_scored_items, read_models_text


def _make_binary_avro(path, n=300, d=5, seed=0, w=None):
    """TrainingExampleAvro fixture with a learnable binary signal. Pass the
    same ``w`` for train and validation splits of one task."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    if w is None:
        w = np.random.default_rng(999).normal(size=d)
    p = 1.0 / (1.0 + np.exp(-(X @ w)))
    y = (rng.uniform(size=n) < p).astype(float)
    records = []
    for i in range(n):
        records.append({
            "uid": f"r{i}", "label": float(y[i]),
            "features": [{"name": f"f{j}", "term": "",
                          "value": float(X[i, j])} for j in range(d)],
            "metadataMap": None, "weight": None, "offset": None,
        })
    write_container(path, schemas.TRAINING_EXAMPLE, records)
    return X, y


GAME_SCHEMA = {
    "name": "GameRecord", "type": "record", "namespace": "t",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "response", "type": "double"},
        {"name": "offset", "type": ["null", "double"], "default": None},
        {"name": "weight", "type": ["null", "double"], "default": None},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}],
         "default": None},
        {"name": "globalFeatures",
         "type": {"type": "array", "items": schemas.FEATURE}},
        {"name": "userFeatures",
         "type": {"type": "array", "items": "FeatureAvro"}},
    ],
}


def _make_game_avro(path, n=400, n_users=8, d_g=6, d_u=3, seed=0):
    rng = np.random.default_rng(seed)
    w_rng = np.random.default_rng(777)  # same true model across splits
    w_g = w_rng.normal(size=d_g)
    W_u = w_rng.normal(size=(n_users, d_u))
    records = []
    for i in range(n):
        u = int(rng.integers(0, n_users))
        xg = rng.normal(size=d_g)
        xu = rng.normal(size=d_u)
        margin = xg @ w_g + xu @ W_u[u]
        y = float(rng.uniform() < 1.0 / (1.0 + np.exp(-margin)))
        records.append({
            # seed-unique uids: multi-part fixtures must not collide
            "uid": f"s{seed}_{i}", "response": y, "offset": None,
            "weight": None,
            "metadataMap": {"userId": f"user{u}"},
            "globalFeatures": [{"name": f"g{j}", "term": "",
                                "value": float(xg[j])} for j in range(d_g)],
            "userFeatures": [{"name": f"u{j}", "term": "",
                              "value": float(xu[j])} for j in range(d_u)],
        })
    write_container(path, GAME_SCHEMA, records)


class TestLegacyDriver:
    def test_logistic_lbfgs_l2_end_to_end(self, tmp_path):
        train = str(tmp_path / "train.avro")
        _make_binary_avro(train, seed=0)
        validate = str(tmp_path / "validate.avro")
        _make_binary_avro(validate, seed=1)
        out = str(tmp_path / "out")
        legacy_main([
            "--training-data-directory", train,
            "--validating-data-directory", validate,
            "--output-directory", out,
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "10,1,0.1",
            "--num-iterations", "40",
            "--data-validation-type", "VALIDATE_FULL",
        ])
        models = read_models_text(os.path.join(out, "output"))
        assert len(models) == 3
        metrics = json.loads(open(os.path.join(out, "metrics.json")).read())
        assert len(metrics) == 3
        key = "AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"
        aucs = [m[key] for m in metrics.values() if key in m]
        assert max(aucs) > 0.75  # learnable signal → decent AUC
        assert os.path.exists(os.path.join(out, "best"))

    def test_owlqn_l1_and_tron(self, tmp_path):
        train = str(tmp_path / "train.avro")
        _make_binary_avro(train, n=200, seed=2)
        for i, (opt, reg) in enumerate([("LBFGS", "L1"), ("TRON", "L2")]):
            out = str(tmp_path / f"out{i}")
            legacy_main([
                "--training-data-directory", train,
                "--output-directory", out,
                "--task", "LOGISTIC_REGRESSION",
                "--optimizer", opt,
                "--regularization-type", reg,
                "--regularization-weights", "1",
                "--num-iterations", "30",
            ])
            assert read_models_text(os.path.join(out, "output"))

    def test_tron_l1_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="TRON"):
            parse_args([
                "--training-data-directory", "x",
                "--output-directory", "y",
                "--optimizer", "TRON",
                "--regularization-type", "L1",
            ])

    def test_box_constraints_end_to_end(self, tmp_path):
        """DriverIntegTest constraint combos: --coefficient-box-constraints
        bounds are enforced on the published raw-space model."""
        import json as _json

        from photon_ml_tpu.cli.legacy_driver import LegacyDriver, parse_args

        train = str(tmp_path / "train.avro")
        _make_binary_avro(train, n=250, seed=6)
        constraints = _json.dumps([
            {"name": "f0", "term": "", "lowerBound": -0.05,
             "upperBound": 0.05},
            {"name": "f1", "term": "", "upperBound": 0.0},
        ])
        driver = LegacyDriver(parse_args([
            "--training-data-directory", train,
            "--output-directory", str(tmp_path / "out"),
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "0.01",
            "--num-iterations", "50",
            "--coefficient-box-constraints", constraints,
        ]))
        driver.run()
        glm = driver.models[0].model
        imap = driver.train_data.index_map
        w = np.asarray(glm.coefficients.means)
        from photon_ml_tpu.io.index_map import feature_key
        i0 = imap.index_of(feature_key("f0"))
        i1 = imap.index_of(feature_key("f1"))
        assert i0 >= 0 and i1 >= 0  # -1 would silently index w[-1]
        assert -0.05 - 1e-6 <= w[i0] <= 0.05 + 1e-6
        assert w[i1] <= 1e-6
        # unconstrained features moved freely
        assert np.abs(w).max() > 0.06

    def test_validate_per_iteration(self, tmp_path):
        """testRunWithDataValidationPerIteration analog: every optimizer
        iteration's model snapshot is evaluated on the validation split and
        logged; the event carries the per-iteration metric list."""
        from photon_ml_tpu.cli.legacy_driver import LegacyDriver, parse_args
        from photon_ml_tpu.utils.events import PhotonOptimizationLogEvent

        w = np.random.default_rng(999).normal(size=5)
        train = str(tmp_path / "train.avro")
        _make_binary_avro(train, n=250, seed=4, w=w)
        validate = str(tmp_path / "validate.avro")
        _make_binary_avro(validate, n=120, seed=5, w=w)
        driver = LegacyDriver(parse_args([
            "--training-data-directory", train,
            "--validating-data-directory", validate,
            "--output-directory", str(tmp_path / "out"),
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "1",
            "--num-iterations", "25",
            "--validate-per-iteration", "true",
        ]))
        events = []
        driver.register_listener(events.append)
        driver.run()
        opt_events = [e for e in events
                      if isinstance(e, PhotonOptimizationLogEvent)]
        assert len(opt_events) == 1
        per_iter = opt_events[0].per_iteration_metrics
        k = driver.models[0].result.iterations
        assert per_iter is not None and len(per_iter) == k + 1
        key = "AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"
        # training improves the metric from the zero model to the optimum
        assert per_iter[-1][key] > per_iter[0][key]
        # final snapshot's metrics == the model's validation metrics
        assert per_iter[-1][key] == pytest.approx(
            driver.per_lambda_metrics[1.0][key], abs=1e-6)

    def test_diagnostics_produced(self, tmp_path):
        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_binary_avro(train, n=400, d=3, seed=3)
        _make_binary_avro(validate, n=150, d=3, seed=4)
        out = str(tmp_path / "out")
        legacy_main([
            "--training-data-directory", train,
            "--validating-data-directory", validate,
            "--output-directory", out,
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "1",
            "--num-iterations", "8",
            "--diagnostic-mode", "ALL",
        ])
        html = open(os.path.join(out, "diagnostic-report.html")).read()
        assert "Hosmer-Lemeshow" in html
        assert "Learning curves" in html
        assert os.path.exists(os.path.join(out, "diagnostic-report.txt"))

    def test_normalization_standardization(self, tmp_path):
        train = str(tmp_path / "train.avro")
        _make_binary_avro(train, n=250, seed=5)
        out = str(tmp_path / "out")
        legacy_main([
            "--training-data-directory", train,
            "--output-directory", out,
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "1",
            "--normalization-type", "STANDARDIZATION",
            "--num-iterations", "30",
            "--summarization-output-dir", str(tmp_path / "summary"),
        ])
        assert read_models_text(os.path.join(out, "output"))
        assert os.path.exists(
            str(tmp_path / "summary" / "part-00000.avro"))


class TestGameDrivers:
    def test_game_train_then_score(self, tmp_path):
        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_game_avro(train, seed=0)
        _make_game_avro(validate, n=150, seed=1)
        out = str(tmp_path / "game-out")
        game_main([
            "--train-input-dirs", train,
            "--validate-input-dirs", validate,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:30,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations",
            "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:30,1e-7,1.0,1,LBFGS,L2",
            "--evaluator-type", "AUC",
        ])
        best_dir = os.path.join(out, "best")
        assert os.path.isdir(os.path.join(best_dir, "fixed-effect", "fixed"))
        assert os.path.isdir(
            os.path.join(best_dir, "random-effect", "perUser"))

        score_out = str(tmp_path / "score-out")
        # Comma-separated multi-input scoring (the plural flag's contract;
        # the reference scoring driver shares GAMEDriver input resolution).
        score_main([
            "--input-data-dirs", f"{validate},{train}",
            "--game-model-input-dir", best_dir,
            "--output-dir", score_out,
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--random-effect-id-set", "userId",
            "--evaluator-type", "AUC",
        ])
        scores = load_scored_items(
            os.path.join(score_out, "scores", "part-00000.avro"))
        assert len(scores) == 150 + 400  # both inputs scored
        assert all(np.isfinite(r["predictionScore"]) for r in scores)

    def test_multiprocess_scoring_matches_single(self, tmp_path):
        """--num-processes/--process-id on the scoring driver: each process
        scores its round-robin share of the part files and writes its own
        scores part; combined output equals a single-process run (scoring
        is per-Spark-partition in the reference, Driver.scala:122-146)."""
        data_dir = tmp_path / "parts"
        data_dir.mkdir()
        _make_game_avro(str(data_dir / "part-00000.avro"), n=120, seed=40)
        _make_game_avro(str(data_dir / "part-00001.avro"), n=90, seed=41)
        _make_game_avro(str(data_dir / "part-00002.avro"), n=70, seed=42)
        out = str(tmp_path / "train-out")
        game_main([
            "--train-input-dirs", str(data_dir),
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:20,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations",
            "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:20,1e-7,1.0,1,LBFGS,L2",
            "--model-output-mode", "BEST",
        ])
        best = os.path.join(out, "best")
        common = [
            "--input-data-dirs", str(data_dir),
            "--game-model-input-dir", best,
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--random-effect-id-set", "userId",
        ]
        single_out = str(tmp_path / "score-single")
        score_main(common + ["--output-dir", single_out])
        multi_out = str(tmp_path / "score-multi")
        for pid in range(2):
            score_main(common + [
                "--output-dir", multi_out,
                "--num-processes", "2", "--process-id", str(pid)])

        def by_uid(d):
            out = {}
            for f in sorted(os.listdir(os.path.join(d, "scores"))):
                for r in load_scored_items(
                        os.path.join(d, "scores", f)):
                    out[r["uid"]] = r["predictionScore"]
            return out

        s1, s2 = by_uid(single_out), by_uid(multi_out)
        assert len(os.listdir(os.path.join(multi_out, "scores"))) == 2
        assert set(s1) == set(s2) and len(s1) == 120 + 90 + 70
        for uid, v in s1.items():
            np.testing.assert_allclose(s2[uid], v, rtol=1e-6, atol=1e-7,
                                       err_msg=uid)
        # evaluators are refused under multi-process scoring
        with pytest.raises(ValueError, match="combined output"):
            score_main(common + [
                "--output-dir", str(tmp_path / "score-ev"),
                "--evaluator-type", "AUC",
                "--num-processes", "2", "--process-id", "0"])

    def test_game_blocks_on_disk_matches_in_ram(self, tmp_path):
        """--random-effect-blocks-dir routes RE block builds through the
        streamed memmap builder; training metrics must match the in-RAM
        path and the block files must really land on disk."""
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=300, seed=9)
        args = [
            "--train-input-dirs", train,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:30,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations",
            "perUser:userId,user,1,-,-,-,identity",
            "--random-effect-optimization-configurations",
            "perUser:30,1e-7,1.0,1,LBFGS,L2",
            "--model-output-mode", "NONE",
        ]
        out_a = str(tmp_path / "in-ram")
        game_main(args + ["--output-dir", out_a])
        blocks = str(tmp_path / "blocks")
        out_b = str(tmp_path / "on-disk")
        game_main(args + ["--output-dir", out_b,
                          "--random-effect-blocks-dir", blocks,
                          "--random-effect-block-buckets", "2"])
        assert any(f.endswith(".f32")
                   for f in os.listdir(os.path.join(blocks, "perUser")))
        rec_a = json.loads(open(os.path.join(out_a, "metrics.json")).read())
        rec_b = json.loads(open(os.path.join(out_b, "metrics.json")).read())
        objs_a = [s["objective"] for s in rec_a["grid"][0]["states"]]
        objs_b = [s["objective"] for s in rec_b["grid"][0]["states"]]
        np.testing.assert_allclose(objs_b, objs_a, rtol=1e-4)

    def test_game_grid_selects_best(self, tmp_path):
        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_game_avro(train, n=250, seed=2)
        _make_game_avro(validate, n=120, seed=3)
        out = str(tmp_path / "out")
        game_main([
            "--train-input-dirs", train,
            "--validate-input-dirs", validate,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures",
            "--updating-sequence", "fixed",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:20,1e-7,10,1,LBFGS,L2;fixed:20,1e-7,0.01,1,LBFGS,L2",
            "--evaluator-type", "AUC",
            "--model-output-mode", "ALL",
        ])
        # grid of 2 → two saved grid models + best
        assert os.path.isdir(os.path.join(out, "output", "grid-0"))
        assert os.path.isdir(os.path.join(out, "output", "grid-1"))
        assert os.path.isdir(os.path.join(out, "best"))


def _numpy_recompute_scores(model_dir: str, records: list[dict]) -> np.ndarray:
    """Independent score recomputation straight from the saved model's avro
    files and the raw input records — shares NO model/score code with the
    driver (only the low-level avro container reader). The offline referent
    of the reference's scoring integ test
    (integTest/.../cli/game/scoring/DriverTest.scala).
    """
    from photon_ml_tpu.io.avro import read_directory

    section_of_shard = {"global": ["globalFeatures"],
                        "user": ["userFeatures"]}

    def coef_map(rec):
        return {(f["name"], f["term"]): float(f["value"])
                for f in rec["means"]}

    def margin(rec_features, coefs):
        m = coefs.get(("(INTERCEPT)", ""), 0.0)
        for f in rec_features:
            m += float(f["value"]) * coefs.get((f["name"], f["term"]), 0.0)
        return m

    scores = np.zeros(len(records))
    fixed_root = os.path.join(model_dir, "fixed-effect")
    for name in (sorted(os.listdir(fixed_root))
                 if os.path.isdir(fixed_root) else []):
        shard = open(os.path.join(fixed_root, name, "id-info")
                     ).read().split()[0]
        _, recs = read_directory(
            os.path.join(fixed_root, name, "coefficients"))
        assert len(recs) == 1
        coefs = coef_map(recs[0])
        for i, rec in enumerate(records):
            feats = [f for sec in section_of_shard[shard]
                     for f in rec[sec]]
            scores[i] += margin(feats, coefs)
    re_root = os.path.join(model_dir, "random-effect")
    for name in (sorted(os.listdir(re_root))
                 if os.path.isdir(re_root) else []):
        re_type, shard = open(
            os.path.join(re_root, name, "id-info")).read().split()[:2]
        _, recs = read_directory(
            os.path.join(re_root, name, "coefficients"))
        per_entity = {r["modelId"]: coef_map(r) for r in recs}
        for i, rec in enumerate(records):
            ent = (rec.get("metadataMap") or {}).get(re_type,
                                                     rec.get(re_type))
            coefs = per_entity.get(str(ent))
            if coefs is None:
                continue  # cold entity → no contribution
            feats = [f for sec in section_of_shard[shard]
                     for f in rec[sec]]
            scores[i] += margin(feats, coefs)
    return scores


class TestScoringParitySweep:
    """Score-vs-offline-recomputation parity at sweep breadth: the CLI
    pipeline (train → save avro model → score via scoring driver) must
    reproduce, element-wise, scores recomputed by plain numpy from the raw
    avro records and the saved coefficient files. Reference analog:
    integTest/.../cli/game/scoring/DriverTest.scala."""

    VARIANTS = {
        "fixed_only": dict(
            updating="fixed",
            score_sections="global:globalFeatures",
            score_ids="",
            extra=[]),
        "fixed_re": dict(
            updating="fixed,perUser",
            extra=[
                "--random-effect-data-configurations",
                "perUser:userId,user,1,-,-,-,identity",
                "--random-effect-optimization-configurations",
                "perUser:30,1e-7,1.0,1,LBFGS,L2"]),
        "fixed_re_projected_capped": dict(
            updating="fixed,perUser",
            extra=[
                # index-map projection + active/feature caps: the saved
                # model scatters reduced coefficients back to raw names
                "--random-effect-data-configurations",
                "perUser:userId,user,1,40,-,-,index_map",
                "--random-effect-optimization-configurations",
                "perUser:30,1e-7,1.0,1,LBFGS,L2"]),
        "fixed_factored": dict(
            updating="fixed,perUserFactored",
            extra=[
                "--random-effect-data-configurations",
                "perUserFactored:userId,user,1,-,-,-,identity",
                "--factored-random-effect-optimization-configurations",
                "perUserFactored:20,1e-7,1.0,1,LBFGS,L2"
                ":20,1e-7,0.1,1,LBFGS,L2:2,2"]),
    }

    @pytest.mark.parametrize("variant", sorted(VARIANTS))
    def test_cli_scores_match_offline_recompute(self, tmp_path, variant):
        from photon_ml_tpu.io.avro import read_container

        cfg = self.VARIANTS[variant]
        train = str(tmp_path / "train.avro")
        score_in = str(tmp_path / "score.avro")
        _make_game_avro(train, n=300, seed=30)
        _make_game_avro(score_in, n=120, seed=31)
        out = str(tmp_path / "out")
        game_main([
            "--train-input-dirs", train,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", cfg["updating"],
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:30,1e-7,0.1,1,LBFGS,L2",
            *cfg["extra"],
        ])
        best_dir = os.path.join(out, "best")

        score_out = str(tmp_path / "score-out")
        score_main([
            "--input-data-dirs", score_in,
            "--game-model-input-dir", best_dir,
            "--output-dir", score_out,
            "--feature-shard-id-to-feature-section-keys-map",
            cfg.get("score_sections",
                    "global:globalFeatures|user:userFeatures"),
            "--random-effect-id-set", cfg.get("score_ids", "userId"),
        ])
        scored = load_scored_items(
            os.path.join(score_out, "scores", "part-00000.avro"))
        _, records = read_container(score_in)
        assert len(scored) == len(records)
        by_uid = {r["uid"]: r["predictionScore"] for r in scored}

        offline = _numpy_recompute_scores(best_dir, records)
        for i, rec in enumerate(records):
            np.testing.assert_allclose(
                by_uid[rec["uid"]], offline[i], rtol=2e-4, atol=2e-4,
                err_msg=f"{variant}: row {i} uid={rec['uid']}")


class TestOffHeapIndexMapFlow:
    """FeatureIndexingJob → --offheap-indexmap-dir consumption, both driver
    families (InputFormatFactory.scala:49-60, GAMEDriver.scala:90-97)."""

    def test_legacy_driver_consumes_offheap_store(self, tmp_path):
        train = str(tmp_path / "train.avro")
        X, y = _make_binary_avro(train, n=250, seed=7)
        index_dir = str(tmp_path / "index")
        index_main([
            "--input-paths", train,
            "--output-dir", index_dir,
            "--num-partitions", "3",
            "--format", "TRAINING_EXAMPLE",
            "--offheap", "true",
        ])
        out = str(tmp_path / "out")
        legacy_main([
            "--training-data-directory", train,
            "--output-directory", out,
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "1",
            "--num-iterations", "30",
            "--offheap-indexmap-dir", index_dir,
            "--offheap-indexmap-num-partitions", "3",
        ])
        models = read_models_text(os.path.join(out, "output"))
        assert models
        # the map actually served lookups: learned dim == store size
        from photon_ml_tpu.io.index_map import OffHeapIndexMap
        oh = OffHeapIndexMap(index_dir, namespace="global")
        (lam, glm), = models
        assert len(glm.coefficients.means) == len(oh)

    def test_game_driver_consumes_offheap_store(self, tmp_path):
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=200, seed=8)
        index_dir = str(tmp_path / "index")
        index_main([
            "--input-paths", train,
            "--output-dir", index_dir,
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--num-partitions", "2",
            "--offheap", "true",
        ])
        out = str(tmp_path / "out")
        game_main([
            "--train-input-dirs", train,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:20,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations", "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:20,1e-7,1.0,1,LBFGS,L2",
            "--offheap-indexmap-dir", index_dir,
        ])
        assert os.path.isdir(os.path.join(out, "best", "fixed-effect",
                                          "fixed"))


class TestScoringOffHeap:
    def test_scoring_driver_consumes_offheap_store(self, tmp_path):
        """The scoring driver's --offheap-indexmap-dir path: train with
        in-heap maps, score with the pre-built off-heap store — scores
        must match an in-heap scoring run exactly."""
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=150, seed=31)
        index_dir = str(tmp_path / "index")
        index_main([
            "--input-paths", train,
            "--output-dir", index_dir,
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--num-partitions", "2",
            "--offheap", "true",
        ])
        out = str(tmp_path / "game-out")
        game_main([
            "--train-input-dirs", train,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:15,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations", "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:15,1e-7,1.0,1,LBFGS,L2",
            "--offheap-indexmap-dir", index_dir,
        ])
        common = [
            "--input-data-dirs", train,
            "--game-model-input-dir", os.path.join(out, "best"),
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--random-effect-id-set", "userId",
        ]
        score_main(common + ["--output-dir", str(tmp_path / "s1"),
                             "--offheap-indexmap-dir", index_dir])
        score_main(common + ["--output-dir", str(tmp_path / "s2")])
        s1 = load_scored_items(
            os.path.join(str(tmp_path / "s1"), "scores", "part-00000.avro"))
        s2 = load_scored_items(
            os.path.join(str(tmp_path / "s2"), "scores", "part-00000.avro"))
        np.testing.assert_allclose(
            [r["predictionScore"] for r in s1],
            [r["predictionScore"] for r in s2], rtol=1e-6)


class TestMultipleEvaluators:
    """DriverTest.multipleEvaluatorTypeProvider analog: every requested
    evaluator runs per CD sweep and lands in validation_metrics; the FIRST
    drives best-model selection (CoordinateDescent.scala:245-255)."""

    @pytest.mark.parametrize("task,ev", [
        ("LINEAR_REGRESSION", "RMSE,SQUARED_LOSS"),
        ("LOGISTIC_REGRESSION",
         "LOGISTIC_LOSS,AUC,precision@1:userId,precision@5:userId"),
        ("LOGISTIC_REGRESSION", "AUC,AUC:userId"),
        ("POISSON_REGRESSION", "POISSON_LOSS"),
    ])
    def test_multiple_evaluators_with_full_model(self, tmp_path, task, ev):
        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            parse_args as game_parse,
        )

        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_game_avro(train, n=200, seed=11)
        _make_game_avro(validate, n=100, seed=12)
        driver = GameTrainingDriver(game_parse([
            "--train-input-dirs", train,
            "--validate-input-dirs", validate,
            "--output-dir", str(tmp_path / "out"),
            "--task-type", task,
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:15,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations", "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:15,1e-7,1.0,1,LBFGS,L2",
            "--evaluator-type", ev,
            "--model-output-mode", "NONE",
        ]))
        result = driver.run()
        expected = [x.strip() for x in ev.split(",")]
        vm = result.states[-1].validation_metrics
        assert vm is not None and sorted(vm) == sorted(expected)
        assert all(np.isfinite(v) for v in vm.values()), vm
        # first evaluator drives selection
        assert result.best_metric == pytest.approx(
            max(s.validation_metrics[expected[0]] for s in result.states)
            if expected[0] in ("AUC",) or expected[0].startswith("precision")
            else min(s.validation_metrics[expected[0]]
                     for s in result.states))

    def test_sharded_evaluator_unknown_id_type_raises(self, tmp_path):
        """shardedEvaluatorOfUnknownIdTypeProvider analog: AUC:unknownId
        must fail loudly, not score garbage."""
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=80, seed=13)
        with pytest.raises(ValueError, match="nonexistentId"):
            game_main([
                "--train-input-dirs", train,
                "--validate-input-dirs", train,
                "--output-dir", str(tmp_path / "out"),
                "--task-type", "LOGISTIC_REGRESSION",
                "--feature-shard-id-to-feature-section-keys-map",
                "global:globalFeatures",
                "--updating-sequence", "fixed",
                "--num-iterations", "1",
                "--fixed-effect-data-configurations", "fixed:global,1",
                "--fixed-effect-optimization-configurations",
                "fixed:10,1e-7,0.1,1,LBFGS,L2",
                "--evaluator-type", "AUC:nonexistentId",
                "--model-output-mode", "NONE",
            ])


class TestInterceptMap:
    """DriverTest.testFixedEffectsWith/WithoutIntercept +
    testRandomEffectsWithPartialIntercept analogs: the per-shard intercept
    map controls whether (INTERCEPT) enters each shard's feature space."""

    def _run(self, tmp_path, intercept_map):
        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            parse_args as game_parse,
        )

        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=150, seed=21)
        driver = GameTrainingDriver(game_parse([
            "--train-input-dirs", train,
            "--output-dir", str(tmp_path / "out"),
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--feature-shard-id-to-intercept-map", intercept_map,
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:10,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations", "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:10,1e-7,1.0,1,LBFGS,L2",
            "--model-output-mode", "NONE",
        ]))
        driver.run()
        return driver

    def test_intercept_on_by_default(self, tmp_path):
        from photon_ml_tpu.io.index_map import INTERCEPT_KEY

        driver = self._run(tmp_path, "")
        assert INTERCEPT_KEY in driver.index_maps["global"]
        assert INTERCEPT_KEY in driver.index_maps["user"]
        assert len(driver.index_maps["global"]) == 6 + 1

    def test_intercept_off(self, tmp_path):
        from photon_ml_tpu.io.index_map import INTERCEPT_KEY

        driver = self._run(tmp_path, "global:false|user:false")
        assert INTERCEPT_KEY not in driver.index_maps["global"]
        assert len(driver.index_maps["global"]) == 6

    def test_partial_intercept(self, tmp_path):
        from photon_ml_tpu.io.index_map import INTERCEPT_KEY

        driver = self._run(tmp_path, "global:true|user:false")
        assert INTERCEPT_KEY in driver.index_maps["global"]
        assert INTERCEPT_KEY not in driver.index_maps["user"]


class TestFeatureIndexingCli:
    def test_game_mode(self, tmp_path, capsys):
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=50, seed=4)
        index_main([
            "--input-paths", train,
            "--output-dir", str(tmp_path / "index"),
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--num-partitions", "2",
        ])
        outp = capsys.readouterr().out
        assert "global:" in outp and "user:" in outp


class TestCheckpointResume:
    def test_game_checkpoint_and_resume(self, tmp_path):
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=200, seed=5)
        ckpt = str(tmp_path / "ckpt")
        args = [
            "--train-input-dirs", train,
            "--output-dir", str(tmp_path / "out1"),
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures",
            "--updating-sequence", "fixed",
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:15,1e-7,0.1,1,LBFGS,L2",
            "--checkpoint-dir", ckpt,
        ]
        game_main(args)
        from photon_ml_tpu.utils.checkpoint import CheckpointManager
        mgr = CheckpointManager(ckpt)
        assert mgr.latest_step() == 2
        # resume: second run starts from the snapshot (no iterations left →
        # model published straight from restored states)
        args[args.index(str(tmp_path / "out1"))] = str(tmp_path / "out2")
        game_main(args)
        import os
        assert os.path.isdir(os.path.join(str(tmp_path / "out2"), "best"))

    def test_mid_sweep_checkpoints_and_quarantine_summary(self, tmp_path):
        """--checkpoint-every-coordinates lands mid-sweep snapshots, and a
        coordinate that exhausts --recovery-quarantine-after is frozen,
        the run completes, and metrics.json reports it."""
        from photon_ml_tpu.utils import faults
        from photon_ml_tpu.utils.checkpoint import CheckpointManager

        faults.disarm_all()
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=200, seed=7)
        ckpt = str(tmp_path / "ckpt")
        out = str(tmp_path / "out")
        # the per-user coordinate (index 1) fails in both sweeps: budget 1
        # quarantines it at the first exhausted update
        faults.arm("cd.update", "raise", tag="0.1")
        faults.arm("cd.update", "raise", tag="1.1")
        try:
            game_main([
                "--train-input-dirs", train,
                "--output-dir", out,
                "--task-type", "LOGISTIC_REGRESSION",
                "--feature-shard-id-to-feature-section-keys-map",
                "global:globalFeatures|user:userFeatures",
                "--updating-sequence", "fixed,perUser",
                "--num-iterations", "2",
                "--fixed-effect-data-configurations", "fixed:global,1",
                "--fixed-effect-optimization-configurations",
                "fixed:15,1e-7,0.1,1,LBFGS,L2",
                "--random-effect-data-configurations",
                "perUser:userId,user,1",
                "--random-effect-optimization-configurations",
                "perUser:15,1e-7,1,1,LBFGS,L2",
                "--checkpoint-dir", ckpt,
                "--checkpoint-every-coordinates", "1",
                "--recovery-policy", "skip",
                "--recovery-max-retries", "0",
                "--recovery-quarantine-after", "1",
            ])
        finally:
            faults.disarm_all()
        with open(os.path.join(out, "metrics.json")) as fh:
            record = json.load(fh)
        assert record["quarantined"] == ["perUser"]
        assert record["grid"][0]["quarantined"] == ["perUser"]
        # only fixed-effect updates landed in the training record
        assert {s["coordinate"]
                for s in record["grid"][0]["states"]} == {"fixed"}
        # mid-sweep snapshots exist and the newest carries the quarantine
        mgr = CheckpointManager(ckpt)
        assert len(mgr.all_steps()) >= 2
        snap = mgr.restore()
        assert snap["quarantined"] == ["perUser"]
        assert os.path.isdir(os.path.join(out, "best"))

    def test_dated_inputs(self, tmp_path):
        day_dir = tmp_path / "data" / "daily" / "2026" / "07" / "01"
        day_dir.mkdir(parents=True)
        _make_game_avro(str(day_dir / "part-00000.avro"), n=150, seed=6)
        out = str(tmp_path / "out")
        game_main([
            "--train-input-dirs", str(tmp_path / "data"),
            "--train-date-range", "20260630-20260702",
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures",
            "--updating-sequence", "fixed",
            "--num-iterations", "1",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:15,1e-7,0.1,1,LBFGS,L2",
        ])
        import os
        assert os.path.isdir(os.path.join(out, "best"))


class TestLibsvmToAvro:
    def test_convert_then_train(self, tmp_path):
        """dev-scripts/libsvm_text_to_trainingexample_avro.py analog: a
        LibSVM file converts to TrainingExampleAvro that the legacy driver
        trains on, reproducing the direct-LibSVM run's model."""
        from photon_ml_tpu.cli.libsvm_to_avro import main as convert_main

        rng = np.random.default_rng(17)
        n, d = 120, 5
        X = rng.normal(size=(n, d))
        w = rng.normal(size=d)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w)))).astype(int)
        libsvm = str(tmp_path / "data.libsvm")
        with open(libsvm, "w") as fh:
            for i in range(n):
                feats = " ".join(f"{j+1}:{X[i, j]:.6f}" for j in range(d))
                fh.write(f"{'+1' if y[i] else '-1'} {feats}\n")
        avro = str(tmp_path / "data.avro")
        convert_main(["--input-path", libsvm, "--output-path", avro,
                      "--feature-dimension", str(d)])

        out_a = str(tmp_path / "out-avro")
        legacy_main([
            "--training-data-directory", avro,
            "--output-directory", out_a,
            "--task", "LOGISTIC_REGRESSION",
            "--regularization-weights", "1",
            "--num-iterations", "30",
        ])
        out_l = str(tmp_path / "out-libsvm")
        legacy_main([
            "--training-data-directory", libsvm,
            "--output-directory", out_l,
            "--task", "LOGISTIC_REGRESSION",
            "--input-file-format", "LIBSVM",
            "--feature-dimension", str(d),
            "--regularization-weights", "1",
            "--num-iterations", "30",
        ])
        (lam_a, glm_a), = read_models_text(os.path.join(out_a, "output"))
        (lam_l, glm_l), = read_models_text(os.path.join(out_l, "output"))
        wa = np.asarray(glm_a.coefficients.means, np.float64)
        wl = np.asarray(glm_l.coefficients.means, np.float64)
        # same optimum up to coefficient ordering (name-sorted vs index)
        np.testing.assert_allclose(sorted(wa), sorted(wl), atol=1e-4)

    def test_raw_labels_preserved(self, tmp_path):
        """--binarize-labels false keeps regression targets raw (the
        reference script keeps float labels; integer labels binarize)."""
        from photon_ml_tpu.cli.libsvm_to_avro import main as convert_main
        from photon_ml_tpu.io.avro import read_records

        libsvm = str(tmp_path / "reg.libsvm")
        with open(libsvm, "w") as fh:
            fh.write("3.7 1:0.5\n-2.25 2:1.0\n")
        avro = str(tmp_path / "reg.avro")
        convert_main(["--input-path", libsvm, "--output-path", avro,
                      "--feature-dimension", "2",
                      "--binarize-labels", "false"])
        recs = read_records(avro)
        assert [r["label"] for r in recs] == [3.7, -2.25]
        # literal 1-based feature names from the file
        assert recs[0]["features"][0]["name"] == "1"
        assert recs[1]["features"][0]["name"] == "2"


def _write_wide_libsvm(path, hot, w_true, seed, n, scale=1.0, shift=0.0,
                       label_rule=None):
    """Hot-column wide LibSVM fixture shared by the wide-sparse tests."""
    r = np.random.default_rng(seed)
    k = len(hot)
    with open(path, "w") as fh:
        for _ in range(n):
            x = r.normal(size=k) * scale + shift
            y = (1 if (x @ w_true) > 0 else -1) if label_rule is None \
                else label_rule(x)
            feats = " ".join(f"{int(j)}:{v:.5f}"
                             for j, v in zip(sorted(hot), x))
            fh.write(f"{'+1' if y > 0 else '-1'} {feats}\n")


class TestWideSparse:
    def test_legacy_driver_wide_sparse_trains_via_ell(self, tmp_path):
        """A feature space past the dense threshold must train through the
        ELL layout — the driver never densifies N x D on the host."""
        from photon_ml_tpu.data.batch import EllBatch
        from photon_ml_tpu.game.dataset import DENSE_FEATURE_THRESHOLD

        d = DENSE_FEATURE_THRESHOLD + 100
        rng = np.random.default_rng(23)
        libsvm = str(tmp_path / "wide.libsvm")
        w_true = rng.normal(size=8)
        hot = rng.choice(d, size=8, replace=False) + 1  # 1-based
        _write_wide_libsvm(libsvm, hot, w_true, seed=23, n=200)
        driver = LegacyDriver(parse_args([
            "--training-data-directory", libsvm,
            "--output-directory", str(tmp_path / "out"),
            "--task", "LOGISTIC_REGRESSION",
            "--input-file-format", "LIBSVM",
            "--feature-dimension", str(d),
            "--regularization-weights", "1",
            "--num-iterations", "15",
        ]))
        driver.run()
        assert isinstance(driver._batch(driver.train_data), EllBatch)
        w = np.asarray(driver.models[0].model.coefficients.means)
        assert np.all(np.isfinite(w)) and np.abs(w).max() > 0

    def test_ragged_wide_sparse_trains_over_the_default_mesh(
            self, tmp_path, monkeypatch):
        """Rows of uneven length past the dense threshold, elastic net:
        ``run`` installs the eight-device mesh, so the fit goes through
        ``run_glm_shard_map`` on a layout of several blocks of slots dealt
        over the shards, and gives what one device gives."""
        from photon_ml_tpu.data.batch import EllBatch
        from photon_ml_tpu.game.dataset import DENSE_FEATURE_THRESHOLD
        from photon_ml_tpu.parallel import mesh as mesh_mod

        d = DENSE_FEATURE_THRESHOLD + 100
        rng = np.random.default_rng(35)
        hot = rng.choice(d, size=40, replace=False) + 1  # 1-based
        w_true = rng.normal(size=40)
        libsvm = str(tmp_path / "ragged.libsvm")
        with open(libsvm, "w") as fh:
            for _ in range(300):
                at = np.sort(rng.choice(40, size=rng.integers(1, 31),
                                        replace=False))
                x = rng.normal(size=len(at))
                feats = " ".join(f"{int(hot[j])}:{v:.5f}"
                                 for j, v in zip(at, x))
                fh.write(f"{'+1' if x @ w_true[at] > 0 else '-1'} {feats}\n")
        hot = np.sort(hot)

        def fit(out):
            driver = LegacyDriver(parse_args([
                "--training-data-directory", libsvm,
                "--output-directory", str(tmp_path / out),
                "--task", "LOGISTIC_REGRESSION",
                "--input-file-format", "LIBSVM",
                "--feature-dimension", str(d),
                "--regularization-type", "ELASTIC_NET",
                "--regularization-weights", "2",
                "--num-iterations", "6",
            ]))
            driver.run()
            return driver

        from photon_ml_tpu.parallel import distributed

        routed = []
        real = distributed.run_glm_shard_map

        def seen(problem, batch, mesh, *a, **kw):
            routed.append((type(batch), len(batch.tail),
                           mesh.shape["data"]))
            return real(problem, batch, mesh, *a, **kw)

        monkeypatch.setattr(distributed, "run_glm_shard_map", seen)
        on_mesh = fit("mesh")
        assert routed and all(r == (EllBatch, routed[0][1], 8)
                              for r in routed) and routed[0][1] >= 2
        monkeypatch.setattr(mesh_mod, "setup_default_mesh",
                            lambda *a, **kw: mesh_mod.set_default_mesh(None))
        del routed[:]
        alone = fit("alone")
        assert not routed
        w_mesh, w_alone = (
            np.asarray(drv.models[0].model.coefficients.means)
            for drv in (on_mesh, alone))
        assert np.abs(w_alone).max() > 0
        np.testing.assert_allclose(w_mesh, w_alone, rtol=1e-3, atol=1e-5)
        np.testing.assert_array_equal(w_mesh == 0.0, w_alone == 0.0)

    def test_wide_sparse_with_standardization(self, tmp_path):
        """Sparse summarization feeds STANDARDIZATION on a wide shard: the
        normalization context builds from sparse statistics and training
        stays in the ELL layout end-to-end."""
        rng = np.random.default_rng(29)
        d = 5000
        libsvm = str(tmp_path / "wide.libsvm")
        hot = rng.choice(d, size=6, replace=False) + 1
        _write_wide_libsvm(libsvm, hot, np.ones(6), seed=29, n=150,
                           scale=10.0, shift=3.0,
                           label_rule=lambda x: 1 if x.sum() > 18 else -1)
        driver = LegacyDriver(parse_args([
            "--training-data-directory", libsvm,
            "--output-directory", str(tmp_path / "out"),
            "--task", "LOGISTIC_REGRESSION",
            "--input-file-format", "LIBSVM",
            "--feature-dimension", str(d),
            "--regularization-weights", "0.1",
            "--num-iterations", "20",
            "--normalization-type", "STANDARDIZATION",
        ]))
        driver.run()
        w = np.asarray(driver.models[0].model.coefficients.means)
        assert np.all(np.isfinite(w))
        # only the hot columns (and intercept) should carry weight
        nz = np.flatnonzero(np.abs(w) > 1e-8)
        expected = set((hot - 1).tolist()) | {d}  # intercept last
        assert set(nz.tolist()) <= expected
        assert len(nz) >= 6


    def test_wide_sparse_validation_metrics(self, tmp_path):
        """The validate stage's fused grid evaluator runs the whole lambda
        grid over an ELL validation batch (wide shard) with sane AUC."""
        from photon_ml_tpu.data.batch import EllBatch

        rng = np.random.default_rng(31)
        d = 5000
        hot = rng.choice(d, size=6, replace=False) + 1
        w_true = rng.normal(size=6)
        train = str(tmp_path / "train.libsvm")
        validate = str(tmp_path / "validate.libsvm")
        _write_wide_libsvm(train, hot, w_true, seed=1, n=250)
        _write_wide_libsvm(validate, hot, w_true, seed=2, n=120)
        driver = LegacyDriver(parse_args([
            "--training-data-directory", train,
            "--validating-data-directory", validate,
            "--output-directory", str(tmp_path / "out"),
            "--task", "LOGISTIC_REGRESSION",
            "--input-file-format", "LIBSVM",
            "--feature-dimension", str(d),
            "--regularization-weights", "10,1,0.1",
            "--num-iterations", "25",
        ]))
        driver.run()
        assert isinstance(driver._validation_batch(), EllBatch)
        key = "AREA_UNDER_RECEIVER_OPERATOR_CHARACTERISTICS"
        assert len(driver.per_lambda_metrics) == 3
        assert max(m[key] for m in driver.per_lambda_metrics.values()) > 0.8


class TestFactoredDriver:
    def test_factored_coordinate_via_cli(self, tmp_path):
        """DriverTest's factored-random-effect path: the CLI parses
        coordId:reCfg:latentCfg:mfCfg, builds a FactoredRandomEffectCoordinate
        over an identity-projected dataset, and publishes latent + projection
        factors in the best model."""
        train = str(tmp_path / "train.avro")
        _make_game_avro(train, n=250, seed=41)
        out = str(tmp_path / "out")
        game_main([
            "--train-input-dirs", train,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUserFac",
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:15,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations",
            "perUserFac:userId,user,1,-1,0,-1,identity",
            "--factored-random-effect-optimization-configurations",
            "perUserFac:10,1e-7,1.0,1,LBFGS,L2"
            ":10,1e-7,0.1,1,LBFGS,L2:2,2",
            "--model-output-mode", "NONE",
        ])
        # re-run through the object API to inspect the published model
        from photon_ml_tpu.cli.game_training_driver import (
            GameTrainingDriver,
            parse_args as game_parse,
        )
        from photon_ml_tpu.game.models import FactoredRandomEffectModel

        driver = GameTrainingDriver(game_parse([
            "--train-input-dirs", train,
            "--output-dir", str(tmp_path / "out2"),
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "perUserFac",
            "--num-iterations", "1",
            "--random-effect-data-configurations",
            "perUserFac:userId,user,1,-1,0,-1,identity",
            "--factored-random-effect-optimization-configurations",
            "perUserFac:10,1e-7,1.0,1,LBFGS,L2"
            ":10,1e-7,0.1,1,LBFGS,L2:2,2",
            "--model-output-mode", "NONE",
        ]))
        result = driver.run()
        model = result.model.models["perUserFac"]
        assert isinstance(model, FactoredRandomEffectModel)
        # latent_dim x d_user (3 features + intercept)
        assert model.projection.shape == (2, 4)
        assert np.all(np.isfinite(np.asarray(model.projection)))
        assert np.all(np.isfinite(np.asarray(model.coefficients_latent)))


class TestGameMetricsOutput:
    def test_metrics_json_written(self, tmp_path):
        """GAME training persists the per-grid-point objective/validation
        record (the legacy driver's metrics.json analog)."""
        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_game_avro(train, n=150, seed=51)
        _make_game_avro(validate, n=80, seed=52)
        out = str(tmp_path / "out")
        game_main([
            "--train-input-dirs", train,
            "--validate-input-dirs", validate,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures",
            "--updating-sequence", "fixed",
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:10,1e-7,1,1,LBFGS,L2;fixed:10,1e-7,0.01,1,LBFGS,L2",
            "--evaluator-type", "AUC",
            "--model-output-mode", "NONE",
        ])
        rec = json.load(open(os.path.join(out, "metrics.json")))
        assert rec["best"]["metric"] is not None
        assert len(rec["grid"]) == 2
        for g in rec["grid"]:
            assert len(g["states"]) == 2  # 2 CD iterations x 1 coordinate
            for s in g["states"]:
                assert np.isfinite(s["objective"])
                assert "AUC" in s["validation_metrics"]


class TestDownSampling:
    def test_fixed_effect_down_sampling_via_cli(self, tmp_path):
        """The opt-config's 4th field (downSamplingRate < 1) engages the
        per-update sampler on the fixed coordinate
        (DistributedOptimizationProblem.runWithSampling analog) and still
        produces a learnable model."""
        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_game_avro(train, n=400, seed=61)
        _make_game_avro(validate, n=150, seed=62)
        out = str(tmp_path / "out")
        game_main([
            "--train-input-dirs", train,
            "--validate-input-dirs", validate,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures",
            "--updating-sequence", "fixed",
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:25,1e-7,0.1,0.5,LBFGS,L2",
            "--evaluator-type", "AUC",
            "--model-output-mode", "NONE",
        ])
        rec = json.load(open(os.path.join(out, "metrics.json")))
        aucs = [s["validation_metrics"]["AUC"]
                for g in rec["grid"] for s in g["states"]]
        assert all(np.isfinite(a) for a in aucs)
        assert max(aucs) > 0.6  # half the negatives dropped, still learns


GAME2_SCHEMA = {
    "name": "GameRecord2", "type": "record", "namespace": "t2",
    "fields": [
        {"name": "uid", "type": ["null", "string"], "default": None},
        {"name": "response", "type": "double"},
        {"name": "offset", "type": ["null", "double"], "default": None},
        {"name": "weight", "type": ["null", "double"], "default": None},
        {"name": "metadataMap",
         "type": ["null", {"type": "map", "values": "string"}],
         "default": None},
        {"name": "globalFeatures",
         "type": {"type": "array", "items": schemas.FEATURE}},
        {"name": "userFeatures",
         "type": {"type": "array", "items": "FeatureAvro"}},
        {"name": "itemFeatures",
         "type": {"type": "array", "items": "FeatureAvro"}},
    ],
}


def _make_game2_avro(path, n=500, n_users=8, n_items=6, d_g=6, d_u=3,
                     d_i=3, seed=0):
    """Two-entity GAME fixture: global + per-user + per-item signal (the
    GameIntegTest per-user/per-song shape)."""
    rng = np.random.default_rng(seed)
    w_rng = np.random.default_rng(778)  # same true model across splits
    w_g = w_rng.normal(size=d_g)
    W_u = w_rng.normal(size=(n_users, d_u))
    W_i = w_rng.normal(size=(n_items, d_i))
    records = []
    for i in range(n):
        u = int(rng.integers(0, n_users))
        it = int(rng.integers(0, n_items))
        xg = rng.normal(size=d_g)
        xu = rng.normal(size=d_u)
        xi = rng.normal(size=d_i)
        margin = xg @ w_g + xu @ W_u[u] + xi @ W_i[it]
        y = float(rng.uniform() < 1.0 / (1.0 + np.exp(-margin)))
        records.append({
            "uid": f"s{i}", "response": y, "offset": None, "weight": None,
            "metadataMap": {"userId": f"user{u}", "itemId": f"item{it}"},
            "globalFeatures": [{"name": f"g{j}", "term": "",
                                "value": float(xg[j])} for j in range(d_g)],
            "userFeatures": [{"name": f"u{j}", "term": "",
                              "value": float(xu[j])} for j in range(d_u)],
            "itemFeatures": [{"name": f"i{j}", "term": "",
                              "value": float(xi[j])} for j in range(d_i)],
        })
    write_container(path, GAME2_SCHEMA, records)


class TestGameDriverSweep:
    """Parametrized GAME-CLI acceptance sweep: coordinate sets x optimizers
    x a 2-point lambda grid, with metric and coefficient-count gates — the
    DriverTest.scala:589+ toy/serious-set analog over the CLI surface."""

    N_USERS, N_ITEMS, D_G, D_U, D_I = 8, 6, 6, 3, 3

    @pytest.mark.parametrize("opt", ["LBFGS", "TRON"])
    @pytest.mark.parametrize(
        "coords", ["fixed", "fixed+re", "fixed+2re"])
    def test_sweep(self, tmp_path, coords, opt):
        from photon_ml_tpu.game.models import (
            FixedEffectModel,
            RandomEffectModel,
        )
        from photon_ml_tpu.io.model_io import load_game_model
        from photon_ml_tpu.optimize.config import TaskType

        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_game2_avro(train, n=500, seed=71)
        _make_game2_avro(validate, n=200, seed=72)
        out = str(tmp_path / "out")

        shard_map_arg = ("global:globalFeatures|user:userFeatures"
                        "|item:itemFeatures")
        seq = ["fixed"]
        args = [
            "--train-input-dirs", train,
            "--validate-input-dirs", validate,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map", shard_map_arg,
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            # 2-point lambda grid on the fixed coordinate
            "--fixed-effect-optimization-configurations",
            f"fixed:25,1e-7,1,1,{opt},L2;fixed:25,1e-7,0.01,1,{opt},L2",
            "--evaluator-type", "AUC",
        ]
        re_data, re_opt = [], []
        if coords in ("fixed+re", "fixed+2re"):
            seq.append("perUser")
            re_data.append("perUser:userId,user,1")
            re_opt.append(f"perUser:25,1e-7,1.0,1,{opt},L2")
        if coords == "fixed+2re":
            seq.append("perItem")
            re_data.append("perItem:itemId,item,1")
            re_opt.append(f"perItem:25,1e-7,1.0,1,{opt},L2")
        if re_data:
            args += ["--random-effect-data-configurations",
                     "|".join(re_data),
                     "--random-effect-optimization-configurations",
                     "|".join(re_opt)]
        args += ["--updating-sequence", ",".join(seq)]
        game_main(args)

        # -- metric gates (per-grid-point record + best-model selection)
        rec = json.load(open(os.path.join(out, "metrics.json")))
        assert len(rec["grid"]) == 2  # the fixed-effect lambda grid
        best_auc = rec["best"]["metric"]
        floor = 0.62 if coords == "fixed" else 0.70
        assert best_auc > floor, (coords, opt, best_auc)
        for g in rec["grid"]:
            for s in g["states"]:
                assert np.isfinite(s["objective"])

        # -- coefficient-count gates (DriverTest's exact-count assertions)
        model, _ = load_game_model(os.path.join(out, "best"),
                                   task=TaskType.LOGISTIC_REGRESSION)
        fixed = model.models["fixed"]
        assert isinstance(fixed, FixedEffectModel)
        assert len(np.asarray(fixed.coefficients.means)) == self.D_G + 1
        if coords in ("fixed+re", "fixed+2re"):
            ru = model.models["perUser"]
            assert isinstance(ru, RandomEffectModel)
            w_u = np.asarray(ru.coefficients)
            assert w_u.shape[0] == self.N_USERS
            assert w_u.shape[1] == self.D_U + 1
        if coords == "fixed+2re":
            ri = model.models["perItem"]
            w_i = np.asarray(ri.coefficients)
            assert w_i.shape[0] == self.N_ITEMS
            assert w_i.shape[1] == self.D_I + 1

    @pytest.mark.parametrize("buckets", [1, 3])
    def test_block_buckets_flag(self, tmp_path, buckets, monkeypatch):
        """--random-effect-block-buckets engages (N, D) bucketing through
        the CLI with identical learning quality to the single block."""
        import photon_ml_tpu.cli.game_training_driver as gtd
        from photon_ml_tpu.io.model_io import load_game_model
        from photon_ml_tpu.optimize.config import TaskType

        # spy: prove the flag actually reaches the dataset build
        built = {}
        orig_build = gtd.build_random_effect_dataset

        def spy(data, cfg, **kw):
            ds = orig_build(data, cfg, **kw)
            built["buckets"] = ds.buckets
            return ds

        monkeypatch.setattr(gtd, "build_random_effect_dataset", spy)

        train = str(tmp_path / "train.avro")
        validate = str(tmp_path / "validate.avro")
        _make_game2_avro(train, n=400, seed=81)
        _make_game2_avro(validate, n=150, seed=82)
        out = str(tmp_path / f"out{buckets}")
        game_main([
            "--train-input-dirs", train,
            "--validate-input-dirs", validate,
            "--output-dir", out,
            "--task-type", "LOGISTIC_REGRESSION",
            "--feature-shard-id-to-feature-section-keys-map",
            "global:globalFeatures|user:userFeatures",
            "--updating-sequence", "fixed,perUser",
            "--num-iterations", "2",
            "--fixed-effect-data-configurations", "fixed:global,1",
            "--fixed-effect-optimization-configurations",
            "fixed:25,1e-7,0.1,1,LBFGS,L2",
            "--random-effect-data-configurations", "perUser:userId,user,1",
            "--random-effect-optimization-configurations",
            "perUser:25,1e-7,1.0,1,LBFGS,L2",
            "--random-effect-block-buckets", str(buckets),
            "--evaluator-type", "AUC",
        ])
        rec = json.load(open(os.path.join(out, "metrics.json")))
        assert rec["best"]["metric"] > 0.70
        # per-entity convergence counts surface in the persisted record
        re_states = [st for g in rec["grid"] for st in g["states"]
                     if st["coordinate"] == "perUser"]
        assert re_states
        for st in re_states:
            counts = st["convergence_counts"]
            assert counts and sum(counts.values()) == self.N_USERS
        model, _ = load_game_model(os.path.join(out, "best"),
                                   task=TaskType.LOGISTIC_REGRESSION)
        w_u = np.asarray(model.models["perUser"].coefficients)
        assert w_u.shape == (self.N_USERS, self.D_U + 1)
        if buckets > 1:
            assert built["buckets"] is not None and len(built["buckets"]) > 1
        else:
            assert built["buckets"] is None
