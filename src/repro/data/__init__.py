from repro.data.manifolds import (  # noqa: F401
    euler_isometric_swiss_roll,
    swiss_roll_classic,
    synthetic_emnist,
)
