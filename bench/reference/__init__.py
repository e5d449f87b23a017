"""Plain references for the benchmark's comparisons.

They import nothing of the program under test: every constant and every
step is written out again here from the definitions (PCG64 LCG root,
XSH-RR, splitmix64, xorshift128, Black-Scholes GBM).
"""
