import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src"), str(Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)

# The tests compile the same small programs for every seed and every run:
# keep them in the program's persistent compilation cache (inside the
# checkout, or where JAX_COMPILATION_CACHE_DIR says).
from chipbench.run import enable_cache  # noqa: E402

enable_cache()
