"""mistral-7b-16l and olmo2-7b-16l: the step programs' rules of
tests/cell_program_checks.py, over the configurations `dense_equal.CELL_FILES`
lists under this file's name (one worker compiles both, once)."""

from cell_program_checks import *  # noqa: F401,F403 - its tests, fixtures and hook
