"""Process-wide counter of explicit device→host fetch sites.

Every INTENTIONAL blocking device→host read in the training path — the CD
fused-epilogue fetch, a lazy tracker/optimizer-history materialization,
the lane-compaction unconverged-mask fetch, a checkpoint snapshot's
payload fetch — calls :func:`record_host_fetch` next to its
``jax.device_get``, tagging WHERE with ``site=...``. The count over a
warm run divided by the number of coordinate updates is
``host_syncs_per_update`` (``tools/photon_status.py`` shows it live):
1.0 means the one-round-trip contract held, and a lazy-materialization
regression (e.g. a tracker forced inside the hot loop) shows up as
> 1.0 — with the per-site breakdown (:func:`host_fetches_by_site`)
naming the culprit.

Since the observability layer landed this module is a thin shim over the
labeled ``host_fetches`` counter in ``photon_ml_tpu.obs.metrics.REGISTRY``
(one storage, two views): :func:`host_fetch_count` is the label-sum, so
the transfer-guard tests keep their exact legacy contract while ``metrics.jsonl`` gets per-site attribution for free. Third-party
callers that never pass ``site`` land under ``"unlabeled"``.

This counts the *instrumented* sites only. A raw ``float()``/
``np.asarray`` sneaked into the hot loop is invisible here by
construction — catching those is the transfer-guard test's job
(tests/test_sync_discipline.py) and photonlint W1xx's.
"""

from __future__ import annotations

from photon_ml_tpu.obs.metrics import REGISTRY

#: Name of the labeled counter in ``obs.metrics.REGISTRY``.
HOST_FETCH_COUNTER = "host_fetches"


def record_host_fetch(n: int = 1, site: str = "unlabeled") -> None:
    REGISTRY.counter(HOST_FETCH_COUNTER).inc(n, site=site)


def reset_host_fetches() -> None:
    REGISTRY.counter(HOST_FETCH_COUNTER).reset()


def host_fetch_count() -> int:
    return int(REGISTRY.counter(HOST_FETCH_COUNTER).total())


def host_fetches_by_site() -> dict[str, int]:
    """Per-site fetch counts; values sum to :func:`host_fetch_count`."""
    return {k: int(v) for k, v in
            REGISTRY.counter(HOST_FETCH_COUNTER).by_label("site").items()}
