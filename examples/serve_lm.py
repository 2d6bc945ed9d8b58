"""Continuous-batching serving demo: requests of mixed lengths share slots.

    PYTHONPATH=src python examples/serve_lm.py --arch falcon-mamba-7b
"""

import sys

from repro.launch.serve import main
from repro.runtime.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    main(sys.argv[1:])
