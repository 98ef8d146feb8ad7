"""kubernetes_tpu: a TPU-native cluster-scheduling framework.

See README.md for the architecture and SURVEY.md for the reference analysis.
Entry points place JAX's persistent compilation cache through
`kubernetes_tpu.utils.compile_cache.enable_compile_cache`; importing the
package sets nothing.
"""
