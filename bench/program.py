"""The system under test, as the harness builds it from a configuration
file: the program's own `ModelConfig`, by the configuration's family
(`bench/families/<family>.py`)."""
from __future__ import annotations

import sys

from bench.common import SRC, load_family

if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


def model_config(c, **override):
    """Configuration file (published key names) -> the program's
    ModelConfig.  `override` sets run settings such as dtypes and remat."""
    return load_family(c["family"]).model_config(c, **override)


def check_tree(weights, cfg) -> None:
    """The benchmark's weight tree has exactly the program's leaves."""
    import jax
    from repro.models import model as MD
    want = MD.model_abstract(cfg)
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), weights)
    exp = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), want)
    if got != exp:
        raise ValueError(f"weight tree differs from the program's: "
                         f"{got} vs {exp}")
