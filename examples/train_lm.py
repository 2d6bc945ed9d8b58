"""End-to-end training driver: train an LM on the synthetic token pipeline.

Default trains a ~20M-param yi-family model for 200 steps on CPU (a few
minutes); ``--preset 100m --steps 300`` is the assignment-scale run.  The
loop exercises the full production path: sharded state on the host mesh,
prefetching resumable data, async checkpoints, resume.

    PYTHONPATH=src python examples/train_lm.py
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300 \
        --ckpt /tmp/lm_ckpt
    # kill it mid-run, then resume exactly:
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300 \
        --ckpt /tmp/lm_ckpt --resume
"""

import sys

from repro.launch.train import main
from repro.runtime.compile_cache import enable_compile_cache

if __name__ == "__main__":
    enable_compile_cache()
    argv = sys.argv[1:]
    if not any(a.startswith("--steps") for a in argv):
        argv += ["--steps", "200"]
    if not any(a.startswith("--preset") for a in argv):
        argv += ["--preset", "smoke"]
    main(argv)
