"""repro: SMI (Streaming Message Interface) rendered for JAX TPU meshes.

The package root imports nothing: the stdlib-only analysis layer
(``repro.analysis`` — the smilint AST rules and ledger verifier,
DESIGN.md §14) must stay importable in jax-free environments (the CI lint
job).
"""
