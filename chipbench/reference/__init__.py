"""Plain references that decide ``correct``. They import nothing of the
program under test: only numpy, JAX's PRNG and the configuration files."""
