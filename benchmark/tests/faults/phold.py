"""Breaks of PHOLD's timed path that the comparison must catch."""

from __future__ import annotations

import jax.numpy as jnp


def half_batch(monkeypatch):
    """Events of odd hosts are taken from the pool and never handled."""
    from shadow_tpu.net import apps

    real_m = apps.PholdApp.handle_msg_matrix

    def matrix(self, state, mv, emitter, params):
        even = (state.host.gid % 2 == 0)[:, None]
        return real_m(self, state, mv.replace(mask=mv.mask & even),
                      emitter, params)

    monkeypatch.setattr(apps.PholdApp, "handle_msg_matrix", matrix)


def answer_altered(monkeypatch):
    """A message goes to the next host over from where its sender's draw
    says."""
    from shadow_tpu.net import apps

    real_pick = apps.PholdApp._pick_dst

    def pick(self, u, my_id):
        d = real_pick(self, u, my_id)
        nxt = (d + 1) % self.num_hosts
        return jnp.where(nxt == my_id, d, nxt)

    monkeypatch.setattr(apps.PholdApp, "_pick_dst", pick)


FAULTS = {"half_batch": half_batch, "answer_altered": answer_altered}
