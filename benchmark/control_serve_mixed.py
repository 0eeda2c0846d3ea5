"""The control of the mixed-length cell's ``correct``: the same served
engine, the same prompts, the runner's own comparison, with the
reference computed in the nearest precision below the configuration's
(every matrix rounded to int8 levels, one scale per output channel). It
must come out NOT correct, by ``logit_abs``.

    python3 benchmark/control_serve_mixed.py --workload mimo-v2.5.serve-mixedlen --seed <n>

``runners/serve_mixed.py`` takes its comparison from
``runners/serve_kinds.py`` whole, so the control is that runner's too
(``control_serve_kinds.py``: both verdicts of one served engine, the
last line ``{"correct": ..., "control_correct": ...}``, exit 0 where the
first is true and the second false); this file is the name the cell's
configuration points at.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.control_serve_kinds import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
