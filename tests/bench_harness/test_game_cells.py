"""PR 33's cell, ``game-ml20m.train``, at a tiny size on the CPU (cut in
users, movies, caps and K; ``tiny.py`` is not edited): the run, the last
line, the control and every planted fault out of their limits, the program
broken underneath, and the generator, the work functions and the reader the
cell stands on."""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, work_game
from benchmark.generators import game_rows, glmix_rows
from benchmark.kinds import game_train
from benchmark.readers import module_time, refit_roofline
from tests.bench_harness import tiny
from tests.bench_harness.test_cells import _check_last_line

CELL = "game-ml20m.train"
BIG_SEED = 2**31 + 4321
NEW_METRICS = {"mf_refit_ms.sweep", "mf_refit_roofline.sweep",
               "mf_evals_per_iter.sweep", "item_lane_fill.sweep",
               "fixed_evals_per_iter.sweep"}


def _tiny_config(full: dict) -> dict:
    """A whole data set of 1,600 users and 120,000 ratings over 300 movies,
    of which this chip holds every fourth user."""
    config = dict(
        full, published=dict(full["published"], ratings=120000, users=1600),
        movies=300, rows_per_chunk=4096, reference_rows_per_block=4096,
        active_rows_cap=32, features_cap=32, max_rows_per_user=250,
        movie_popularity_exponent=1.0, movie_popularity_shift=6.0,
        latent_dim=4)
    counts = game_rows.share_counts(
        config, np.random.default_rng([config["data_seed"], 0]))
    return dict(config, users=len(counts), rows=int(counts.sum()))


@pytest.fixture(scope="module")
def spec() -> harness.Spec:
    full = harness.load_spec(CELL)
    return full._replace(config=_tiny_config(full.config))


def _run(spec, trace=False, trace_dir=None) -> dict:
    return harness.run_cell(spec, BIG_SEED, 0.3, trace, time.perf_counter(),
                            tiny.DEVICE, trace_dir=trace_dir)


def test_the_cell_runs_and_is_correct_at_a_tiny_size(spec, capsys):
    result = _run(spec)
    names = _check_last_line(result, CELL, trace=False)
    assert set(result["metrics"]) == set(names) == {"sweep_s", "setup_s"}
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    assert err.strip().splitlines()[-1] == "correct: True"


def test_a_traced_run_reports_the_cells_layer_metrics(spec, tmp_path):
    result = _run(spec, trace=True, trace_dir=str(tmp_path / "trace"))
    names = _check_last_line(result, CELL, trace=True)
    got = set(result["metrics"])
    assert NEW_METRICS <= set(names)
    assert "evals_per_iter.sweep" not in names  # its filter would mix two
    # no device plane on the CPU: the trace's readers return nothing
    assert got <= set(names) and not got & {
        "device_idle.sweep", "hbm_roofline.sweep", "fe_solve_ms.sweep",
        "re_solve_ms.sweep", "mf_refit_ms.sweep", "mf_refit_roofline.sweep"}
    assert {"compile_s", "lower_s", "block_build_s", "step_mfu.sweep",
            "lane_fill.sweep", "mf_evals_per_iter.sweep",
            "item_lane_fill.sweep", "fixed_evals_per_iter.sweep"} <= got
    value = {n: m["value"] for n, m in result["metrics"].items()}
    assert 0 < value["step_mfu.sweep"] < 100
    assert 1.0 <= value["mf_evals_per_iter.sweep"] <= 4.0
    assert 1.0 <= value["fixed_evals_per_iter.sweep"] <= 4.0
    assert 0 < value["item_lane_fill.sweep"] <= 100
    assert 0 < value["lane_fill.sweep"] <= 100


def test_the_control_and_every_fault_read_over_a_limit(spec):
    state = game_train.build(spec.config, spec.workload, 11,
                             harness.Phases())
    limits = spec.workload["limits"]
    record = game_train.step(state)
    sound = game_train.verify(state, record, limits)
    assert harness.judge(sound), sound
    assert [name for name, _, _ in sound] == list(limits)
    assert len(record["objectives"]) == 4 == len(game_train.SEQUENCE)
    control = game_train.verify(state, game_train.control(state), limits)
    assert not harness.judge(control), control
    assert set(game_train.FAULTS) == {
        "state_unchanged", "half_batch", "no_item_exchange",
        "refit_drops_factor", "stale_projection"}
    for name, fault in game_train.FAULTS.items():
        planted = game_train.verify(state, fault(state), limits)
        assert not harness.judge(planted), (name, planted)


# --- a whole run with the program broken underneath -------------------------


@pytest.fixture
def fresh_traces():
    """A method of the program patched underneath a jitted solve is seen
    only by a fresh trace, and its trace must not outlive the patch."""
    import jax

    from photon_ml_tpu.obs import compile as obs_compile

    def drop():
        jax.clear_caches()
        obs_compile.reset()

    drop()
    yield
    drop()


def _item_scores_reach_nobody(monkeypatch):
    train = game_train.train

    class Silent:
        def __init__(self, coordinate):
            self.coordinate = coordinate

        def __getattr__(self, name):
            return getattr(self.coordinate, name)

        def score(self, state):
            return jnp.zeros(self.coordinate.num_samples, jnp.float32)

    def broken(coords, *rest):
        return train(dict(coords, **{"per-item": Silent(
            coords["per-item"])}), *rest)

    monkeypatch.setattr(game_train, "train", broken)


def _scatter_drops_the_last_factor(monkeypatch):
    from photon_ml_tpu.data.batch import ProjectionRefitBatch

    whole = ProjectionRefitBatch.weighted_feature_sum

    def broken(self, row_scalars):
        return whole(self._replace(blocks=[
            b._replace(latent=b.latent.at[:, -1].set(0.0))
            for b in self.blocks]), row_scalars)

    monkeypatch.setattr(ProjectionRefitBatch, "weighted_feature_sum",
                        broken)


def _the_refit_is_dropped(monkeypatch):
    from photon_ml_tpu.game.coordinate import FactoredRandomEffectCoordinate

    update = FactoredRandomEffectCoordinate.update

    def broken(self, state, extra_scores):
        (coefs, _), tracker = update(self, state, extra_scores)
        return (coefs, state[1]), tracker

    monkeypatch.setattr(FactoredRandomEffectCoordinate, "update", broken)


def _half_of_the_rows_weigh_nothing(monkeypatch):
    """The second half of the rows weighs nothing, the first half twice (the
    fault GLMix's cell plants, under four coordinates)."""
    from photon_ml_tpu.game import dataset

    init = dataset.GameDataset.__post_init__

    def halved(self):
        init(self)
        n = len(self.responses)
        self.weights = np.where(np.arange(n) < n // 2, 2.0, 0.0)

    monkeypatch.setattr(dataset.GameDataset, "__post_init__", halved)


@pytest.mark.parametrize("breaker", [
    _item_scores_reach_nobody, _scatter_drops_the_last_factor,
    _the_refit_is_dropped, _half_of_the_rows_weigh_nothing])
def test_a_run_with_the_program_broken_is_not_correct(spec, breaker,
                                                      monkeypatch,
                                                      fresh_traces):
    breaker(monkeypatch)
    result = _run(spec)
    assert result["correct"] is False, result["checks"]
    assert result["metrics"]  # it ran; only the answer is wrong


# --- the generator ----------------------------------------------------------


def test_the_share_is_every_fourth_user_of_the_whole_data_set(spec):
    config = spec.config
    rng = np.random.default_rng([config["data_seed"], 0])
    whole = glmix_rows.user_counts(120000, 1600, 20, 250, 1.1)
    assert whole.sum() == 120000 and whole.min() == 20
    dealt = whole[np.random.default_rng(
        [config["data_seed"], 0]).permutation(1600)]
    shares = [game_rows.share_counts(
        dict(config, chip_index=i),
        np.random.default_rng([config["data_seed"], 0])) for i in range(4)]
    for i, share in enumerate(shares):
        assert np.array_equal(share, dealt[i::4]) and len(share) == 400
    assert sum(int(s.sum()) for s in shares) == 120000  # the counts add up
    assert np.array_equal(game_rows.share_counts(config, rng), shares[0])
    assert config["rows"] == int(shares[0].sum())


def test_game_rows_keep_their_laws_and_move_only_ids_with_the_seed(spec):
    config = spec.config
    a = game_rows.make_rows(config, BIG_SEED)
    b = game_rows.make_rows(config, BIG_SEED)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    n = config["rows"]
    assert a.X.shape == (n, 65) and np.all(a.X[:, -1] == 1.0)
    assert set(np.unique(a.y)) == {0.0, 1.0} and 0.3 < a.y.mean() < 0.7
    # every user of the share is there, with its count; a movie once a user
    by_user = np.bincount(a.user, minlength=config["users"])
    assert by_user.min() >= 20 and len(by_user) == config["users"] == 400
    pairs = a.user.astype(np.int64) * config["movies"] + a.movie
    assert len(np.unique(pairs)) == n
    assert a.movie.min() >= 0 and a.movie.max() < config["movies"]
    # another seed: the same rows in the same order, the entities' ids
    # dealt anew, the feature columns where they were
    c = game_rows.make_rows(config, BIG_SEED + 1)
    assert np.array_equal(a.X, c.X) and np.array_equal(a.y, c.y)
    assert not np.array_equal(a.user, c.user)
    assert not np.array_equal(a.movie, c.movie)
    assert np.array_equal(a.user_feature, c.user_feature)
    assert np.array_equal(a.movie_feature, c.movie_feature)
    for ids, feature in ((a.user, a.user_feature), (c.user, c.user_feature),
                         (a.movie, a.movie_feature),
                         (c.movie, c.movie_feature)):
        # one id a feature and one feature an id: a relabelling
        both = np.unique(np.stack([ids, feature]), axis=1)
        assert len(np.unique(both[0])) == len(np.unique(both[1])) \
            == both.shape[1]
    assert np.array_equal(np.sort(np.bincount(a.user)),
                          np.sort(np.bincount(c.user)))
    # --seed picks one of the configuration's dealings, and no other
    lanes = config["lane_seeds"]
    assert len(lanes) == 3 == len(set(lanes))
    for k, same in ((3, a), (4, c)):
        d = game_rows.make_rows(config, BIG_SEED + k)
        assert np.array_equal(d.user, same.user)
        assert np.array_equal(d.movie, same.movie)
    third = game_rows.make_rows(config, BIG_SEED + 2)
    assert not np.array_equal(third.user, a.user)
    assert not np.array_equal(third.user, c.user)
    B0 = game_rows.starting_projection(config)
    assert B0.shape == (4, 300) and B0.dtype == np.float32
    assert np.array_equal(B0, game_rows.starting_projection(config))
    assert abs(float(B0.std()) - 0.5) < 0.05  # standard normal / sqrt(K)
    # another data_seed deals the users anew: another share, other rows
    again = dict(config, data_seed=6)
    again["rows"] = int(game_rows.share_counts(
        again, np.random.default_rng([6, 0])).sum())
    other = game_rows.make_rows(again, BIG_SEED)
    assert again["rows"] != n and not np.array_equal(a.y[:99], other.y[:99])
    report = game_rows.describe_rows(a, config)
    assert report["rows"] == n and report["users"] == 400
    assert report["users_at_the_cap"] == int(np.sum(by_user >= 32))
    assert report["passive_rows_per_user_side"] == int(
        np.sum(np.maximum(by_user - 32, 0)))
    assert report["movies_hit"] == len(np.unique(a.movie))
    with pytest.raises(ValueError, match="the configuration states"):
        game_rows.make_rows(dict(config, rows=n + 1), 1)


def test_the_configurations_file_states_what_the_generator_makes():
    config = harness.load_spec(CELL).config
    assert config["architecture"] is None
    published = config["published"]
    assert (published["ratings"], published["users"],
            published["movies_rated"]) == (20000263, 138493, 26744)
    assert config["movies"] == 26744 and config["latent_dim"] == 32
    assert (config["active_rows_cap"], config["features_cap"],
            config["buckets"]) == (128, 128, 4)
    counts = game_rows.share_counts(
        config, np.random.default_rng([config["data_seed"], 0]))
    assert len(counts) == config["users"] == 34624 == -(-138493 // 4)
    assert int(counts.sum()) == config["rows"] == 5046676
    exponent, shift = game_rows.popularity_constants(26744, 20000263, 67310)
    assert config["movie_popularity_exponent"] == pytest.approx(exponent,
                                                                rel=1e-9)
    assert config["movie_popularity_shift"] == pytest.approx(shift, rel=1e-9)
    law = (np.arange(26744) + shift) ** -exponent
    law /= law.sum()
    assert law[0] * 20000263 == pytest.approx(67310, rel=1e-6)
    assert law[-1] * 20000263 == pytest.approx(1.0, rel=1e-6)
    bench = harness.load_spec(CELL).bench
    entry = {c["name"]: c for c in bench["configs"]}["game-ml20m"]
    assert entry["reduced"] == ["rows", "users"] == sorted(config["reduced"])


# --- the work, and the reader ------------------------------------------------


def test_the_work_of_a_refit_pass_and_of_the_entities_solves():
    # 10 stored values over 4 slots of 3 rows, 2 touched columns, K = 5
    assert work_game.refit_pass_flops(10, 4, 5) == 4 * 10 + 4 * 5 * 4
    assert work_game.refit_pass_bytes(10, 4, 3, 2, 5, 4) == (
        4 * 10 + 4 * 4 + 12 * 3 + 2 * 5 * 4 * 2)
    one = work_game.refit_work(10, 4, 3, 2, 5, 4, 3)
    assert one == {"flops": 3 * 120, "bytes": 3 * (40 + 16 + 36 + 80)}
    assert work_game.entity_work([6, 2], [3, 5], 4) == {
        "flops": 4 * 28, "bytes": 4 * 28}


def test_the_kind_credits_every_coordinates_own_counts(spec):
    state = game_train.build(spec.config, spec.workload, 1,
                             harness.Phases())
    record = game_train.step(state)
    ev, sh = record["evaluations"], state.shapes
    assert set(ev) == {"fixed", "per-user", "per-item", "mf.latent",
                       "mf.refit"}
    assert ev["fixed"] >= 7 and 7 <= ev["mf.refit"] <= 6 * 20
    users = sh["sides"]["per-user"]
    assert len(ev["per-user"]) == len(ev["mf.latent"]) == 400
    assert len(ev["per-item"]) == len(sh["sides"]["per-item"]["ids"])
    # one-hot rows, a movie once a user: as many slots as training rows
    refit = sh["refit"]
    assert refit["slots"] == refit["rows"] == int(users["active"].sum())
    assert refit["cells"] == int((users["active"] ** 2).sum())
    assert refit["columns"] == len(np.unique(
        users["columns"][users["columns"] < 300])) <= 300
    assert record["mf_refit"] == work_game.refit_work(
        refit["cells"], refit["slots"], refit["rows"], refit["columns"], 4,
        4, ev["mf.refit"])
    rows = spec.config["rows"]
    expect = (
        ev["fixed"] * rows * 65 * 4
        + 4 * int(users["cells"] @ ev["per-user"])
        + 4 * int(sh["sides"]["per-item"]["cells"] @ ev["per-item"])
        + 4 * int((users["active"] * 4) @ ev["mf.latent"])
        + record["mf_refit"]["bytes"])
    assert game_train.work(state, record)["bytes"] == expect


def test_the_refits_readers_on_hand_built_events():
    layer = harness.load_spec(CELL).layer_metrics
    modules = [(0, 3_000_000, "jit__minimize_lbfgs_impl(11)"),
               (0, 8_000_000, "jit__factored_refit_impl(12)"),
               (0, 7_000_000, "jit__fit_blocks_impl(13)")]
    context = {"trace": {"steps": 2, "chips": 1, "modules": modules},
               "units_per_step": 1.0,
               "records": [{"mf_refit": {"flops": 1, "bytes": 819e3}},
                           {"mf_refit": {"flops": 1, "bytes": 819e3}},
                           {}],
               "peaks": {"hbm_bytes_per_s": 819e9}}
    assert module_time.read(layer["mf_refit_ms.sweep"], context) \
        == pytest.approx(4.0)
    # the fixed effect's module is the other solver's, not the refit's
    assert module_time.read(layer["fe_solve_ms.sweep"], context) \
        == pytest.approx(1.5)
    # 2 traced steps x 819e3 bytes = 2 us at the peak, over 8 ms
    assert refit_roofline.read(layer["mf_refit_roofline.sweep"], context) \
        == pytest.approx(100 * 2e-6 / 8e-3)
    for without in (dict(context, trace=None), dict(context, records=[{}]),
                    dict(context, trace=dict(context["trace"],
                                             modules=modules[:1]))):
        assert refit_roofline.read(layer["mf_refit_roofline.sweep"],
                                   without) is None


def test_the_new_metrics_files_and_the_cells_lists():
    full = harness.load_spec(CELL)
    for name in NEW_METRICS:
        body = full.layer_metrics[name]
        assert body["workloads"] == [CELL] and body["moves"] == "sweep_s"
    assert full.layer_metrics["mf_evals_per_iter.sweep"]["labels"] == {
        "coordinate": "mf", "site": "optimizer.lbfgs"}
    assert full.layer_metrics["item_lane_fill.sweep"]["labels"] == {
        "coordinate": "per-item"}
    assert full.layer_metrics["fixed_evals_per_iter.sweep"]["labels"] == {
        "coordinate": "fixed", "site": "optimizer.lbfgs"}
    assert harness.end_to_end_names(full) == ["sweep_s", "setup_s"]
    assert {"sweep_s", "device_idle.sweep", "step_mfu.sweep",
            "hbm_roofline.sweep", "fe_solve_ms.sweep", "re_solve_ms.sweep",
            "lane_fill.sweep", "block_build_s", "compile_s",
            "lower_s"} - {"sweep_s"} <= set(full.layer_metrics)
    step = full.workload["step"]
    assert (step["fixed"]["max_iterations"],
            step["per_user"]["max_iterations"],
            step["per_item"]["max_iterations"],
            step["mf"]["latent"]["max_iterations"],
            step["mf"]["refit"]["max_iterations"],
            step["mf"]["inner_iterations"]) == (6, 8, 8, 8, 6, 1)
