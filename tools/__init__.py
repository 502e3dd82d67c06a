"""Dev tooling (fixture writers, on-chip A/Bs).  A package so the tools
and the tests can share measurement harness code."""
