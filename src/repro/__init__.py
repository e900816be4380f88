"""repro: AnalogNets + AON-CiM as a multi-pod JAX framework.

The paper's contribution (noise-robust analog-CiM training, calibrated PCM
simulation, layer-serial accelerator modeling) lives in ``repro.core``;
``repro.models`` scales the technique from the paper's TinyML CNNs to the
10 assigned LM architectures; ``repro.launch`` distributes everything over
the 256/512-chip production meshes. See DESIGN.md / EXPERIMENTS.md.
"""

__version__ = "1.0.0"

import jax as _jax

# Sharding-invariant RNG: with the legacy (non-partitionable) threefry
# lowering, the *values* drawn under jit can depend on the output sharding
# (observed on 2D meshes with a sharded leading dim). A programmed CiM chip
# must be the same chip no matter which mesh programmed it, so the whole
# framework runs with the partitionable lowering (the default in newer JAX).
_jax.config.update("jax_threefry_partitionable", True)

# The recorder's process-wide hooks (compile events, garbage collections)
# are installed when it is first imported: here, so every process that uses
# the program has them.
from repro import obs as _obs  # noqa: E402,F401
