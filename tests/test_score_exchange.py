"""The score and offset exchange under names of the program's own (PR 37):
the jitted offset and bucket-coefficient programs return what the eager
forms they replace returned, bit for bit; the exchange's host spans carry
the coordinate's id; the block build's stages tile its span."""

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import test_sync_discipline as tsd
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.game.dataset import (
    GameDataset,
    RandomEffectDataConfiguration,
    build_fixed_effect_dataset,
    build_random_effect_dataset,
    build_random_effect_dataset_streamed,
    dataset_row_stream,
)
from photon_ml_tpu.game.random_effect import score_random_effect
from photon_ml_tpu.obs import trace
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.optimize.config import TaskType


@pytest.fixture()
def rng():
    return np.random.default_rng(37)


def _dataset(rng, buckets: int, passive: bool):
    """Entities of uneven size; with ``passive`` a cap of 16 active rows
    leaves the larger ones passive rows."""
    data, *_ = tsd.make_game_data(rng, n=900, n_entities=14)
    config = RandomEffectDataConfiguration(
        "userId", "per_user", 1,
        num_active_data_points_upper_bound=16 if passive else None)
    return data, build_random_effect_dataset(data, config,
                                            num_buckets=buckets)


def _eager_offsets(ds, extra):
    """The form ``offsets_with`` had before it was one jitted program."""
    padded = jnp.concatenate([extra, jnp.zeros(1, extra.dtype)])
    if ds.buckets is None:
        return ds.base_offsets + padded[ds.row_ids]
    return [b.base_offsets + padded[b.row_ids] for b in ds.buckets]


def _eager_bucket_coefs(bucket, coefs):
    """The form ``score_random_effect`` cut a bucket's block with."""
    e_b, _, d_b = bucket.X.shape
    nr, start = bucket.num_real, bucket.entity_start
    return jnp.zeros((e_b, d_b), coefs.dtype).at[:nr].set(
        coefs[start:start + nr, :d_b])


@pytest.mark.parametrize("buckets,passive", [
    (1, False), (1, True), (3, False), (3, True)])
def test_the_named_programs_return_what_the_eager_forms_did(
        rng, buckets, passive):
    from photon_ml_tpu.game import random_effect as re_mod

    data, ds = _dataset(rng, buckets, passive)
    assert (ds.buckets is not None) == (buckets > 1)
    assert bool(ds.num_passive) == passive
    extra = jnp.asarray(rng.normal(size=data.num_samples), jnp.float32)
    want, got = _eager_offsets(ds, extra), ds.offsets_with(extra)
    if ds.buckets is None:
        want, got = [want], [got]
    assert len(want) == len(got) == ds.num_blocks
    for w, g in zip(want, got):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    coefs = jnp.asarray(
        rng.normal(size=(ds.num_entities, ds.reduced_dim)), jnp.float32)
    if ds.buckets is not None:
        spans = tuple((b.entity_start, b.num_real, int(b.X.shape[0]),
                       int(b.X.shape[2])) for b in ds.buckets)
        for bucket, got_b in zip(ds.buckets,
                                 re_mod._bucket_coefs(coefs, spans)):
            np.testing.assert_array_equal(
                np.asarray(got_b),
                np.asarray(_eager_bucket_coefs(bucket, coefs)))
    # and the scores gathered from them are the scores scattered by row id
    # (the mesh path's form, which cuts its blocks by the same program)
    gathered = score_random_effect(ds, coefs)
    blocks = ds.buckets if ds.buckets is not None else [ds]
    cut = [_eager_bucket_coefs(b, coefs) for b in blocks] \
        if ds.buckets is not None else [coefs]
    scattered = sum(
        re_mod.score_active(b.X, c, b.row_ids, b.weights, ds.num_samples)
        for b, c in zip(blocks, cut))
    if passive:
        scattered = scattered + re_mod.score_passive(
            ds.passive_X, ds.passive_entity, coefs, ds.passive_row_ids,
            ds.num_samples)
    np.testing.assert_allclose(np.asarray(gathered), np.asarray(scattered),
                               rtol=0, atol=1e-5)


def test_the_exchanges_spans_carry_the_coordinates_id(rng):
    data, *_ = tsd.make_game_data(rng, n=240, n_entities=6)
    coords = tsd._build_coords(data)
    tracer = trace.enable()
    try:
        run_coordinate_descent(
            coords, 1, TaskType.LOGISTIC_REGRESSION,
            jnp.asarray(data.responses), jnp.asarray(data.weights),
            jnp.asarray(data.offsets))
    finally:
        trace.disable()
    events = tracer.events()
    by_name = {name: [e for e in events if e["name"] == name]
               for name in ("re.offsets", "re.score", "cd.dispatch")}
    assert len(by_name["re.offsets"]) == len(by_name["re.score"]) == 1
    (dispatch,) = [e for e in by_name["cd.dispatch"]
                   if e["labels"]["coordinates"] == "perUser"]
    for name in ("re.offsets", "re.score"):
        (span,) = by_name[name]
        # as cd.update spells it, and inside the update's dispatch
        assert span["labels"] == {"coordinate": "perUser", "blocks": 1}
        assert dispatch["ts_us"] <= span["ts_us"] and (
            span["ts_us"] + span["dur_us"]
            <= dispatch["ts_us"] + dispatch["dur_us"])


def _stage_seconds():
    out: dict = {}
    for key, value in REGISTRY.counter("block_build_secs").items().items():
        labels = dict(key)
        out[labels["stage"], labels["coordinate"]] = value
    return out


@pytest.mark.parametrize("streamed", [False, True])
def test_the_builds_stages_tile_its_span(streamed):
    rng = np.random.default_rng(5)
    n, users, movies = 120_000, 900, 400
    user = rng.integers(0, users, n)
    one_hot = sp.csr_matrix(
        (np.ones(n, np.float32), rng.integers(0, movies, n),
         np.arange(n + 1)), shape=(n, movies))
    data = GameDataset(
        responses=(rng.uniform(size=n) < 0.5).astype(np.float64),
        feature_shards={"global": sp.csr_matrix(rng.normal(size=(n, 6))),
                        "per_user": one_hot})
    data.encode_ids("userId", user)
    config = RandomEffectDataConfiguration(
        "userId", "per_user", 1, num_active_data_points_upper_bound=64,
        num_features_to_keep_upper_bound=32)
    REGISTRY.counter("block_build_secs").reset()
    tracer = trace.enable()
    try:
        if streamed:
            ds = build_random_effect_dataset_streamed(
                dataset_row_stream(data, config, chunk_rows=40_000), config,
                raw_dim=movies, num_buckets=3)
        else:
            ds = build_random_effect_dataset(data, config, num_buckets=3)
            build_fixed_effect_dataset(data, "global")
    finally:
        trace.disable()
    events = tracer.events()
    (build,) = [e for e in events if e["name"] == "dataset.build"]
    assert build["labels"]["coordinate"] == "userId"
    assert build["labels"]["rows"] == n
    assert build["labels"]["entities"] == ds.num_entities == users
    seconds = _stage_seconds()
    stages = {stage for stage, coordinate in seconds
              if coordinate == "userId"}
    assert stages == ({"group", "project", "pack", "transfer"} if streamed
                      else {"group", "project", "pack", "passive",
                            "transfer"})
    booked = sum(v for (_, c), v in seconds.items() if c == "userId")
    assert booked == pytest.approx(build["dur_us"] / 1e6, rel=0.02)
    # the counter and the timeline read the same intervals
    for stage in stages:
        spans = [e for e in events if e["name"] == "dataset." + stage]
        assert spans and all(e["depth"] == 1 and e["labels"]["coordinate"]
                             == "userId" for e in spans)
        assert sum(e["dur_us"] for e in spans) / 1e6 == pytest.approx(
            seconds[stage, "userId"], rel=0.02, abs=2e-4)
    if not streamed:
        (fixed,) = [e for e in events if e["name"] == "dataset.fixed"]
        assert fixed["labels"] == {"coordinate": "global", "rows": n,
                                   "cols": 6}
        assert seconds["fixed", "global"] == pytest.approx(
            fixed["dur_us"] / 1e6, rel=0.02, abs=2e-4)
