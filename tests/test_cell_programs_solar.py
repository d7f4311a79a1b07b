"""solar-open2-ep8-4l: the step programs' rules of
tests/cell_program_checks.py, over the configuration `dense_equal.CELL_FILES`
lists under this file's name (one worker compiles it, once)."""

from cell_program_checks import *  # noqa: F401,F403 - its tests, fixtures and hook
