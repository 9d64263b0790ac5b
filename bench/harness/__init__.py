"""The benchmark's harness: discovery by name, the seed's draws, trace
reduction, the plain reference and the result line. Everything one
configuration, traffic mix, cell kind or per-layer metric needs sits in
files of its own, found by name."""
