"""Device time of the operations traced under the trained model's ``mlp``
module (a layer's gated MLP: its name is a piece of an operation's ``tf_op``
in the forward pass, ``jvp(...)/model/layer_<i>/mlp/...``, in the recomputed
forward, ``.../checkpoint/rematted_computation/layer_<i>/mlp/...``, and in
the backward pass, ``transpose(jvp(...))/.../layer_<i>/mlp/...``, alike)
over device busy time. None where the program has no such module."""

from benchmarks.harness import scope_readers

SCOPES = ('mlp',)


def read(obs):
    return scope_readers.scope_share(obs, SCOPES)
