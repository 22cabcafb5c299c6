"""Production mesh construction + MeshPolicy wiring.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required by the dry-run contract.
"""

from __future__ import annotations

import jax

from repro.models.sharding import MeshPolicy


def make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Models below this size pay more in TP activation collectives than TP
# saves in memory; they run pure DP/FSDP with the 'model' axis folded into
# the data axes (§Perf iteration E — measured 230 GiB→~1 GiB wire on
# mamba2-130m train_4k).
TP_MIN_PARAMS = 1_000_000_000


def make_policy(mesh, model_cfg=None, *, seq_parallel: bool = False) -> MeshPolicy:
    """MeshPolicy for a mesh built by make_production_mesh.

    * KV-cache sharding is adaptive: shard the cache's sequence dim when
      the arch's kv-head count doesn't divide the tp axis (DESIGN.md §6).
    * Sub-1B-param models drop TP entirely (the 'model' axis becomes an
      extra FSDP/data axis) — §Perf iteration E, 125× wire reduction.
    * seq_parallel defaults OFF: §Perf iteration B measured it INCREASING
      wire 1.8× under GSPMD (reshard ping-pong at every layer boundary
      outweighs the all-reduce→reduce-scatter saving). Hypothesis refuted;
      kept as an opt-in knob for a future shard_map-explicit version.
    """
    from repro.models.config import param_count

    axes = mesh.axis_names
    dp = tuple(a for a in axes if a in ("pod", "data"))
    tp = "model" if "model" in axes else None
    shard_cache_seq = False
    if model_cfg is not None and tp is not None:
        if param_count(model_cfg) < TP_MIN_PARAMS:
            return MeshPolicy(
                mesh=mesh, dp=dp + (tp,), tp=None,
                shard_cache_seq=False, seq_parallel=False,
            )
        tp_size = mesh.shape[tp]
        shard_cache_seq = model_cfg.n_kv_heads % tp_size != 0
    return MeshPolicy(
        mesh=mesh, dp=dp, tp=tp, shard_cache_seq=shard_cache_seq,
        seq_parallel=seq_parallel and tp is not None,
    )


def make_host_mesh(n_devices: int | None = None, model: int = 1) -> object:
    """Small mesh over the actually-present devices (tests / local runs)."""
    n = n_devices or len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))
