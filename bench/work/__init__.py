"""Operations and bytes of one logical operation, computed from its shapes.

The counts follow the mathematics the configuration states, not the kernel
that implements it, so a change of route or tiling leaves them unchanged.
"""
