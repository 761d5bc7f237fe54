"""Fixtures shared by the serving test files."""
import jax.numpy as jnp
import pytest


@pytest.fixture
def host_copies(monkeypatch) -> list:
    """Record the shape of every array whose ``copy_to_host_async`` is
    called (patched on the concrete array class, whose method shadows
    ``jax.Array``'s)."""
    cls = type(jnp.zeros(()))
    real = cls.copy_to_host_async
    calls = []

    def spy(self):
        calls.append(self.shape)
        return real(self)
    monkeypatch.setattr(cls, "copy_to_host_async", spy)
    return calls
