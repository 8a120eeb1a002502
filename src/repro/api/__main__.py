"""``python -m repro.api`` entry point — see ``repro.api.cli``."""
import sys

from repro.api.cli import main
from repro.launch.compile_cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    sys.exit(main())
