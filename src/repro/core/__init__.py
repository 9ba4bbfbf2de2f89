# The paper's primary contribution: the dynamic averaging protocol.
from repro.core.divergence import (  # noqa: F401
    divergence, sq_distance, local_condition_violated, flat_size,
    tree_mean, tree_weighted_mean, tree_weighted_sum,
    per_learner_sq_distance, per_learner_sq_distance_flat,
)
from repro.core.protocol import DecentralizedLearner, make_protocol  # noqa: F401
from repro.core import operators  # noqa: F401
from repro.core import sync  # noqa: F401  (the staged sync kernel)
