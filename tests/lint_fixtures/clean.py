"""Clean counterexamples: the same shapes of code as the bad fixtures, but
guarded/donated/canonical — plus suppression-comment demonstrations."""

import jax
from jax.sharding import PartitionSpec as P


SPEC = P("data", "model")  # canonical axes: no JL004

# suppression on the same line:
BAD_BUT_WAIVED = P("batch")  # jaxlint: disable=JL004 logical name on purpose

# standalone-comment suppression applies to the next line:
# jaxlint: disable=JL004 logical name on purpose
ALSO_WAIVED = P("batch")


@jax.jit
def static_branches_ok(x, mask=None):
    if mask is not None:      # `is None` test is static: no JL002
        x = x + mask
    if x.ndim == 3:           # shape metadata is static: no JL002
        x = x.reshape(x.shape[0], -1)
    return x


@jax.jit
def static_alias_branches_ok(x):
    dtype = x.dtype           # alias of static metadata stays static
    n = len(x)
    if dtype == "int8":       # no JL002: branch on dtype via alias
        x = x.astype("int32")
    if n > 3:                 # no JL002: branch on len via alias
        x = x[:3]
    return x
