"""Run ``benchmark/run.py``'s ``main`` with the timed path broken
underneath: ``python fault_driver.py <root> <fault> <run.py arguments>``.
The harness, the traffic kind, the reference and the comparison run as
they are; only ``Trainer.fuse_step``'s step object is wrapped.

- ``state_unchanged``: every step computes its loss and then puts the
  parameters and the optimizer's state back as they were;
- ``half_batch``: every step sees only the first half of its batch, and
  takes its mean over that.
"""
import sys


def install(fault):
    from mxnet_tpu import gluon
    from mxnet_tpu.optimizer import _state_rebind, _state_values
    real_fuse = gluon.Trainer.fuse_step

    def fuse_step(self, net, loss_fn, **kw):
        fused = real_fuse(self, net, loss_fn, **kw)
        real_step = fused.step

        def half_batch(x, y, **k):
            n = x.shape[0] // 2
            return real_step(x[:n], y[:n], **k)

        def state_unchanged(x, y, **k):
            params = net._collect_params_with_prefix()
            states = self._updaters[0].states
            keep_p = {n: p.data().copy() for n, p in params.items()
                      if p._data is not None}
            keep_s = {i: _state_values(s) for i, s in states.items()}
            loss = real_step(x, y, **k)
            for n, v in keep_p.items():
                params[n].set_data(v)
            for i, v in keep_s.items():
                _state_rebind(states[i], v)
            return loss

        fused.step = {"half_batch": half_batch,
                      "state_unchanged": state_unchanged}[fault]
        return fused

    gluon.Trainer.fuse_step = fuse_step


if __name__ == "__main__":
    root, fault = sys.argv[1], sys.argv[2]
    sys.path.insert(0, root)
    install(fault)
    from benchmark import run
    sys.exit(run.main(sys.argv[3:]))
