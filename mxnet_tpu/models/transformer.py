"""Decoder-only transformer LM on the Symbol API.

The framework's modern long-sequence model (SURVEY §5.7: the idiomatic
replacement for unrolled RNNs). Attention lowers to the Pallas flash kernel
on TPU (ops/attention.py → ops/pallas/flash_attention.py); the sharded
functional twin used for tp/pp/sp training lives in
mxnet_tpu.parallel.transformer.
"""
from .. import symbol as sym


def _norm(x, kind, dm, name, eps=None):
    """LayerNorm (gamma, beta) or RMSNorm (a plain scale); ``eps`` None
    leaves each op its own default (1e-5 and 1e-6)."""
    gamma = sym.Variable(name + '_gamma', shape=(dm,))
    kw = {} if eps is None else {'eps': eps}
    if kind == 'rms':
        return sym.RMSNorm(data=x, gamma=gamma, name=name, **kw)
    if kind != 'layer':
        raise ValueError("norm %r: 'layer' or 'rms'" % (kind,))
    beta = sym.Variable(name + '_beta', shape=(dm,))
    return sym.LayerNorm(data=x, gamma=gamma, beta=beta, name=name, **kw)


# What a layer may be; ``layers`` of get_symbol gives one such dict a layer
# (keys left out take these values, which are today's block).
LAYER_KINDS = {
    'norm': 'layer',      # 'layer' | 'rms'
    'mixer': 'attention',  # 'attention' | 'short_conv': ShortConv, which
                           # has no position encoding and no heads
    'conv_kernel': 3,     # taps a channel of a 'short_conv' mixer
    'window': 0,          # keys a query sees, its own included; 0: all
    'rope': True,         # False: no position encoding at all (NoPE)
    'rope_base': 10000.0,
    'qk_norm': False,     # RMSNorm of every q and k head before the rotation
    'ffn': 'gelu',        # 'gelu': biased dense GELU | 'swiglu': dense
                          # gated, ffn2(silu(ffn1 x) * ffn3 x), no bias |
                          # 'experts': ExpertFFN
    'ffn_dim': 0,         # this layer's feed-forward width; 0: the model's
    'router_input': 'mixer',  # what an 'experts' router reads: the
                              # 'mixer' input's norm | the 'ffn' input's
}


def _attention(h, num_heads, dm, name, num_kv_heads, use_flash, head_dim,
               kind, eps):
    """q, k, v, attention and o over the normed input ``h``."""
    # GQA (num_kv_heads < num_heads): k/v projections shrink to
    # num_kv_heads*head_dim and the flash kernel streams them narrow
    head_dim = head_dim or dm // num_heads
    dq = head_dim * num_heads
    dkv = dq if not num_kv_heads else head_dim * num_kv_heads
    q = sym.FullyConnected(data=h, num_hidden=dq, flatten=False, no_bias=True,
                           name=name + '_q')
    k = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                           no_bias=True, name=name + '_k')
    v = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                           no_bias=True, name=name + '_v')
    # use_flash=None defers to the op default (True, with the kernel's
    # own on-TPU/shape selection gate) — passing None through would
    # read as falsy and silently pin the einsum path
    att_kw = {} if use_flash is None else {'use_flash': use_flash}
    # today's block names none of these attributes, and its graph stays as
    # it was
    if kind['window']:
        att_kw['window'] = kind['window']
    if kind['rope'] and kind['rope_base'] != LAYER_KINDS['rope_base']:
        att_kw['rope_base'] = kind['rope_base']
    if kind['qk_norm']:
        att_kw['qk_norm'] = True
        if eps is not None:
            att_kw['qk_norm_eps'] = eps
    att = sym.MultiHeadAttention(query=q, key=k, value=v, num_heads=num_heads,
                                 num_kv_heads=num_kv_heads, causal=True,
                                 use_rope=bool(kind['rope']),
                                 name=name + '_attn', **att_kw)
    return sym.FullyConnected(data=att, num_hidden=dm, flatten=False,
                              no_bias=True, name=name + '_o')


def _block(x, num_heads, dm, dff, name, num_kv_heads=0, use_flash=None,
           head_dim=0, kind=LAYER_KINDS, experts=None, eps=None):
    """One pre-norm decoder block of the kinds ``kind`` names:
    ``x + mixer(norm1 x)``, then ``+ ffn(norm2 ...)``. ``experts`` (for ffn
    'experts'): the ExpertFFN attributes, and the layer's width is then one
    expert's."""
    unknown = set(kind) - set(LAYER_KINDS)
    if unknown:
        raise ValueError("layer kinds %s unknown (known: %s)"
                         % (sorted(unknown), sorted(LAYER_KINDS)))
    kind = dict(LAYER_KINDS, **kind)
    dff = kind['ffn_dim'] or dff
    h = _norm(x, kind['norm'], dm, name + '_ln1', eps)
    mixer_in = h
    if kind['mixer'] == 'short_conv':
        taps = kind['conv_kernel']
        mixed = sym.ShortConv(
            data=h, kernel=taps,
            in_weight=sym.Variable(name + '_conv_in_weight',
                                   shape=(3 * dm, dm)),
            conv_weight=sym.Variable(name + '_conv_weight',
                                     shape=(dm, taps)),
            out_weight=sym.Variable(name + '_conv_out_weight',
                                    shape=(dm, dm)),
            name=name + '_conv')
    elif kind['mixer'] == 'attention':
        mixed = _attention(h, num_heads, dm, name, num_kv_heads, use_flash,
                           head_dim, kind, eps)
    else:
        raise ValueError("mixer %r: 'attention' or 'short_conv'"
                         % (kind['mixer'],))
    x = x + mixed
    h = _norm(x, kind['norm'], dm, name + '_ln2', eps)
    if kind['ffn'] == 'experts':
        if kind['router_input'] not in ('mixer', 'ffn'):
            raise ValueError("router_input %r: 'mixer' or 'ffn'"
                             % (kind['router_input'],))
        held = experts.get('experts_held') or experts['num_experts']
        h = sym.ExpertFFN(
            data=h,
            router_data=mixer_in if kind['router_input'] == 'mixer' else h,
            router_weight=sym.Variable(
                name + '_router_weight',
                shape=(experts['num_experts'], dm)),
            gate_weight=sym.Variable(name + '_gate_weight',
                                     shape=(held, dff, dm)),
            up_weight=sym.Variable(name + '_up_weight',
                                   shape=(held, dff, dm)),
            down_weight=sym.Variable(name + '_down_weight',
                                     shape=(held, dm, dff)),
            name=name + '_experts', **experts)[0]
        return x + h
    if kind['ffn'] == 'swiglu':
        gate = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                                  no_bias=True, name=name + '_ffn1')
        up = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                                no_bias=True, name=name + '_ffn3')
        h = sym.broadcast_mul(
            sym.Activation(data=gate, act_type='silu', name=name + '_silu'),
            up, name=name + '_glu')
        h = sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                               no_bias=True, name=name + '_ffn2')
        return x + h
    if kind['ffn'] != 'gelu':
        raise ValueError("ffn %r: 'gelu', 'swiglu' or 'experts'"
                         % (kind['ffn'],))
    h = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                           name=name + '_ffn1')
    h = sym.Activation(data=h, act_type='gelu', name=name + '_gelu')
    h = sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                           name=name + '_ffn2')
    return x + h


def _backbone(num_classes, num_layers, num_heads, model_dim, ffn_dim,
              num_kv_heads, use_flash, head_dim=0, layers=None, experts=None,
              final_norm='layer', head_bias=True, norm_eps=None,
              tie_head=False):
    if layers is None:
        layers = [LAYER_KINDS] * num_layers
    if len(layers) != num_layers:
        raise ValueError("layers names %d layers, num_layers is %d"
                         % (len(layers), num_layers))
    if tie_head and head_bias:
        raise ValueError("tie_head: the table has no bias to share "
                         "(head_bias=False)")
    data = sym.Variable('data')          # (batch, seq_len) int ids
    tied = {'weight': sym.Variable('embed_weight',
                                   shape=(num_classes, model_dim))} \
        if tie_head else {}
    x = sym.Embedding(data=data, input_dim=num_classes,
                      output_dim=model_dim, name='embed', **tied)
    for i, kind in enumerate(layers):
        x = _block(x, num_heads, model_dim, ffn_dim, 'layer%d' % i,
                   num_kv_heads=num_kv_heads, use_flash=use_flash,
                   head_dim=head_dim, kind=kind, experts=experts,
                   eps=norm_eps)
    x = _norm(x, final_norm, model_dim, 'lnf', norm_eps)
    pred = sym.Reshape(data=x, shape=(-1, model_dim))
    # a tied head multiplies by the table itself: one leaf, whose gradient
    # is the sum of both uses
    return sym.FullyConnected(data=pred, num_hidden=num_classes,
                              no_bias=not head_bias, name='pred', **tied)


def get_symbol(num_classes=32000, seq_len=512, num_layers=4, num_heads=8,
               model_dim=512, ffn_dim=2048, num_kv_heads=0, use_flash=None,
               scalar_loss=False, head_dim=0, layers=None, experts=None,
               final_norm='layer', head_bias=True, norm_eps=None,
               tie_head=False, **kwargs):
    """Decoder LM symbol. scalar_loss=True emits a MakeLoss mean-NLL head
    (output ``loss``) instead of SoftmaxOutput — the (batch*seq, vocab)
    probability output is the right inference surface but costs a fresh
    device buffer per step, which benchmark/training loops that only need
    the loss avoid (docs/perf.md LSTM caveat). The head is
    ``softmax_cross_entropy`` (the closed form: a float32 logsumexp over
    the vocabulary less the label's logit, and a backward that builds no
    one-hot) over the number of rows, which is counted in float32 from the
    label's shape and folds to a constant.

    The block's kinds, all defaulting to the dense block this builder
    always built: ``layers``, one dict a layer over ``LAYER_KINDS`` (norm;
    the mixer: attention with its window, rope, rope_base and qk_norm, or
    a gated short convolution; the feed-forward: biased GELU, dense gated
    SwiGLU or experts, and its width where a layer's differs), so that
    window + RoPE layers, global NoPE layers and convolution layers sit in
    one model; ``head_dim`` where it is not model_dim / num_heads;
    ``experts``, the ``ExpertFFN`` attributes (num_experts, experts_held,
    first_expert, top_k, route, ...) of the layers whose ffn is 'experts',
    with ``ffn_dim`` one expert's width; ``final_norm``; ``norm_eps`` for
    every norm of the model (None: each op's default); ``head_bias`` False
    for a bias-free head, ``tie_head`` for one that multiplies by the
    embedding table. This is the only place the block is built for
    training: the decode builders (serving/generate/model.py) and the
    sharded step (parallel/transformer.py) build the dense LayerNorm block
    alone and say so when handed another."""
    pred = _backbone(num_classes, num_layers, num_heads, model_dim, ffn_dim,
                     num_kv_heads, use_flash, head_dim, layers, experts,
                     final_norm, head_bias, norm_eps, tie_head)
    label = sym.Reshape(data=sym.Variable('softmax_label'), shape=(-1,))
    if scalar_loss:
        rows = sym.sum(sym.ones_like(sym.Cast(label, dtype='float32')))
        nll = sym._div(sym.softmax_cross_entropy(pred, label), rows)
        return sym.MakeLoss(nll, name='loss')
    return sym.SoftmaxOutput(data=pred, label=label, name='softmax')
