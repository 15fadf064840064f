"""Platform-aware autotuner: measured rig profiles + cost-model
geometry resolution (the RankMap split — measure the platform, then
plan from a cost model).

* :mod:`tune.defaults` — the one table of hand-pinned geometry
  (what ``--tune off`` runs; lint rule TDA120 anchors on it),
* :mod:`tune.profile` — the seeded ``tda tune`` profiling pass and
  the versioned, rig-tagged ``RigProfile`` JSON artifact,
* :mod:`tune.resolve` — the cost model joining profiles against the
  closed-form comm/reshard accounting, and the per-knob resolver
  (explicit flag > resolved > default, every choice with a WHY).

jax-free at package level: the cluster's host processes resolve
geometry without a device runtime.
"""

from tpu_distalg.tune import defaults
from tpu_distalg.tune.profile import (
    ProfileError,
    SCHEMA_VERSION,
    build_profile,
    load_profile,
    measure_backend_init,
    measure_collective,
    measure_rig,
    newest_profile,
    profile_crc,
    save_profile,
)
from tpu_distalg.tune.resolve import (
    KNOBS,
    Choice,
    Resolution,
    Workload,
    emit_resolution,
    resolve,
    schedule_seconds,
)

__all__ = [
    "Choice", "KNOBS", "ProfileError", "Resolution", "SCHEMA_VERSION",
    "Workload", "build_profile", "defaults", "emit_resolution",
    "load_profile", "measure_backend_init", "measure_collective",
    "measure_rig",
    "newest_profile", "profile_crc", "resolve", "save_profile",
    "schedule_seconds",
]
