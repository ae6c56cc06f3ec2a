"""The benchmark's own tests (not part of the repo's tier-1 `tests/`):

    python -m pytest benchmarks/tests -q -p no:cacheprovider

They need no accelerator: the cells run as `--rehearse-cpu` rehearsals in
child processes."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
