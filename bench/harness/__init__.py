"""The chip benchmark's harness: everything it measures with and compares
against lives here, apart from the program under test (``src/repro``)."""
