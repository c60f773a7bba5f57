import os

# Tests must see the plain 1-device CPU backend (the dry-run, and ONLY the
# dry-run, simulates 512 devices — in its own subprocess).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# no persistent compilation cache in tests, in this process or the CLI
# children it starts (repro.launch.train turns one on for real runs)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_enable_x64", False)
