"""The trace reader's cases in tier-1 (``PERF.md`` section 7 (i)): what
``perf/tests/test_trace_scopes.py`` (PR 67: the scope of an operation from
its ``tf_op``, self time with each instant counted once, the two recorded
v5e traces) and ``perf/tests/test_scope_entries.py`` (PR 68: the entries
that read the names) hold, imported and run here — 3 s, and the reader
imports no JAX."""

from perf.tests.test_scope_entries import *  # noqa: F401,F403
from perf.tests.test_trace_scopes import *  # noqa: F401,F403

# PR 67's count of the entries (7 of 100) is that PR's: a file under
# ``perf/`` that exists is a ``benchmark`` PR's to edit, and
# ``test_the_scope_entries_name_the_reader_and_split_the_busy_time`` holds
# the same rules to the entries as they are
del test_the_seven_entries_name_the_reader_and_split_the_busy_time  # noqa: F821
