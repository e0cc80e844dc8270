"""Small shared utilities (``util``) and the host span names (``trace``).

Import the submodules directly: the package itself loads nothing, so the
data layer can use ``trace`` without importing JAX.
"""
