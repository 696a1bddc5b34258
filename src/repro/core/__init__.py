"""The paper's primary contribution: the Hippo sparse index, in JAX."""
from repro.core import bitmap, cost, grouping, histogram, predicate  # noqa: F401
from repro.core.hippo import HippoIndex  # noqa: F401
from repro.core.partition import ShardedHippoIndex, ShardSpec  # noqa: F401
from repro.core.index import (CompactBatchResult, HippoConfig,  # noqa: F401
                              HippoState)
from repro.core.predicate import Predicate  # noqa: F401
