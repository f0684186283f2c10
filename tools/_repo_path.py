"""Make the repo root importable from tools/ scripts.

Running a script puts ITS directory, not the repo root, first on
sys.path; tools extend sys.path here rather than ask for PYTHONPATH:

    import _repo_path  # noqa: F401  (must precede `import jax`)
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
