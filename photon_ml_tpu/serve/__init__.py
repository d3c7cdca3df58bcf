"""Always-on GAME scoring service.

The batch scoring driver loads a model, scores one dataset, and exits;
this package keeps the model resident and serves scoring requests over
a socket, sustained:

- :mod:`photon_ml_tpu.serve.protocol` — versioned NDJSON request
  protocol over TCP/unix sockets (same endpoint grammar as the
  telemetry plane) plus the blocking client used by tests and drills.
- :mod:`photon_ml_tpu.serve.batcher` — bounded request queue feeding an
  adaptive micro-batcher; overload sheds (counted on
  ``serve_shed{reason}``), never blocks the device loop.
- :mod:`photon_ml_tpu.serve.tiers` — tiered per-entity coefficient
  store: device-resident hot block sized by an HBM budget, host LRU for
  the recently-evicted tail, the loaded model block behind both.
- :mod:`photon_ml_tpu.serve.scoring` — the shared model-load +
  Σ-coordinate-score core (the batch driver is a thin client of it) and
  the bucketed serving scorer built on the tier stores.
- :mod:`photon_ml_tpu.serve.service` — the socket service: reader
  threads, the device loop, latency/qps gauges that ride the heartbeat
  stream into ``photon_status``, and the graceful-drain exit contract
  (SIGTERM → drain → exit 75) the supervisor understands.

Entrypoint: ``tools/photon_serve.py`` (or
``python -m photon_ml_tpu.serve.service``, the module form
``photon_supervise --module`` relaunches).
"""

from photon_ml_tpu.serve.scoring import (  # noqa: F401
    load_scoring_model,
    resolve_index_maps,
    score_game_dataset,
)
