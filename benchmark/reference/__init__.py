"""Plain references the benchmark's ``correct`` rests on."""
