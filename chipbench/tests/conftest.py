import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT), str(Path(__file__).parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

jax.config.update("jax_platform_name", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
