"""The families that bring a reduction rule of their own register it when
they are imported (`benchmarks/families/bailing_hybrid.py`:
`scope_roofline_pct`), as `run.load_cell` imports a cell's family before
any metric is reduced.  The registry's tests read `trace.RULES` without
loading a cell, so the family is imported here, once, for this
directory's tests."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import benchmarks.families.bailing_hybrid  # noqa: E402,F401
