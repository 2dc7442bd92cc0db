"""The benchmark's own yardstick: graph and weight draws, required work,
peaks, trace reduction and the plain reference. Nothing here imports the
program under test (``repro``)."""
