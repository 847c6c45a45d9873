"""Nothing is registered here any more. Until PR 36 this file marked
strict-xfail the tests of benchmark/tests that asserted the table as it
was when they were written; they are restated where they live, for the
table as it grows. The empty table stays because
tests/test_benchmark_harness.py, which a ``benchmark`` PR may not edit,
imports ``OUTGROWN`` from here to decide what tier-1 collects: with
nothing in it, every such test runs there under its own name. The PR
that next edits that file drops its import and this file."""

OUTGROWN: dict = {}
