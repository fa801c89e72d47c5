"""One file per kernel a tenant can submit, found by the kernel's name.

Each holds the launch geometry, the input memory layout drawn from a
seeded generator, where the kernel writes, and a plain numpy oracle of
what it computes.  ``build(n)`` is the one call into the program: the
binary is what the tenant submits, made with the system's own
toolchain (assembler or DSL compiler).
"""
