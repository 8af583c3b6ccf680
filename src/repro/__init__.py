"""repro — N:M structured-sparse matmul as a first-class feature of a
multi-pod JAX training/serving framework (TPU adaptation of Titopoulos et
al., "Optimizing Structured-Sparse Matrix Multiplication in RISC-V Vector
Processors", 2025)."""

__version__ = "1.0.0"
