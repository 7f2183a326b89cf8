"""The benchmark's own machinery: lookup by name, the commit window,
the result comparison, compile clock and profiler-trace reduction."""
