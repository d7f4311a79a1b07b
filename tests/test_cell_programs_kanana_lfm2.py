"""kanana-2-30b-a3b-7l and lfm2-24b-a2b-9l: the step programs' rules of
tests/cell_program_checks.py, over the configurations `dense_equal.CELL_FILES`
lists under this file's name (one worker compiles both, once)."""

from cell_program_checks import *  # noqa: F401,F403 - its tests, fixtures and hook
