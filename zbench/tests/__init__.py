"""The harness's own tests: ``python3 -m pytest zbench/tests -q`` on the
CPU. They import nothing of ``tests/`` and need no chip."""
