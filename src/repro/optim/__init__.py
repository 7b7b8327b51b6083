from repro.optim.adamw import (  # noqa: F401
    AdamWConfig,
    adamw_update,
    cosine_schedule,
    global_norm,
)
