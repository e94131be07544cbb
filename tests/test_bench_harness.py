"""The benchmark harness's own tests (``bench/tests``), collected into
the repository's test run: the harness's arithmetic, traffic, trace
reduction and result line; the faults the comparison that decides
``correct`` has to catch; and the readers of the program's spans and
scopes.  All run on the CPU at smoke size, with no chip."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "bench" / "tests"))

from test_faults import *  # noqa: E402,F401,F403
from test_harness import *  # noqa: E402,F401,F403
from test_program_trace import *  # noqa: E402,F401,F403
