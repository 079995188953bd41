"""Decoder-only transformer LM on the Symbol API.

The framework's modern long-sequence model (SURVEY §5.7: the idiomatic
replacement for unrolled RNNs). Attention lowers to the Pallas flash kernel
on TPU (ops/attention.py → ops/pallas/flash_attention.py); the sharded
functional twin used for tp/pp/sp training lives in
mxnet_tpu.parallel.transformer.
"""
from .. import symbol as sym


def _norm(x, kind, dm, name):
    """LayerNorm (gamma, beta) or RMSNorm (eps 1e-6, a plain scale)."""
    gamma = sym.Variable(name + '_gamma', shape=(dm,))
    if kind == 'rms':
        return sym.RMSNorm(data=x, gamma=gamma, name=name)
    if kind != 'layer':
        raise ValueError("norm %r: 'layer' or 'rms'" % (kind,))
    beta = sym.Variable(name + '_beta', shape=(dm,))
    return sym.LayerNorm(data=x, gamma=gamma, beta=beta, name=name)


# What a layer may be; ``layers`` of get_symbol gives one such dict a layer
# (keys left out take these values, which are today's block).
LAYER_KINDS = {
    'norm': 'layer',      # 'layer' | 'rms'
    'window': 0,          # keys a query sees, its own included; 0: all
    'rope': True,         # False: no position encoding at all (NoPE)
    'rope_base': 10000.0,
    'ffn': 'gelu',        # 'gelu': biased dense GELU | 'experts': ExpertFFN
}


def _block(x, num_heads, dm, dff, name, num_kv_heads=0, use_flash=None,
           head_dim=0, kind=LAYER_KINDS, experts=None):
    """One pre-norm decoder block of the kinds ``kind`` names. ``experts``
    (for ffn 'experts'): the ExpertFFN attributes, and ``dff`` is then one
    expert's width; the router reads the attention's normed input."""
    unknown = set(kind) - set(LAYER_KINDS)
    if unknown:
        raise ValueError("layer kinds %s unknown (known: %s)"
                         % (sorted(unknown), sorted(LAYER_KINDS)))
    kind = dict(LAYER_KINDS, **kind)
    h = _norm(x, kind['norm'], dm, name + '_ln1')
    att_in = h
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
    # today's block names neither attribute, and its graph stays as it was
    if kind['window']:
        att_kw['window'] = kind['window']
    if kind['rope'] and kind['rope_base'] != LAYER_KINDS['rope_base']:
        att_kw['rope_base'] = kind['rope_base']
    att = sym.MultiHeadAttention(query=q, key=k, value=v, num_heads=num_heads,
                                 num_kv_heads=num_kv_heads, causal=True,
                                 use_rope=bool(kind['rope']),
                                 name=name + '_attn', **att_kw)
    att = sym.FullyConnected(data=att, num_hidden=dm, flatten=False,
                             no_bias=True, name=name + '_o')
    x = x + att
    h = _norm(x, kind['norm'], dm, name + '_ln2')
    if kind['ffn'] == 'experts':
        held = experts.get('experts_held') or experts['num_experts']
        h = sym.ExpertFFN(
            data=h, router_data=att_in,
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
    if kind['ffn'] != 'gelu':
        raise ValueError("ffn %r: 'gelu' or 'experts'" % (kind['ffn'],))
    h = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                           name=name + '_ffn1')
    h = sym.Activation(data=h, act_type='gelu', name=name + '_gelu')
    h = sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                           name=name + '_ffn2')
    return x + h


def _backbone(num_classes, num_layers, num_heads, model_dim, ffn_dim,
              num_kv_heads, use_flash, head_dim=0, layers=None, experts=None,
              final_norm='layer', head_bias=True):
    if layers is None:
        layers = [LAYER_KINDS] * num_layers
    if len(layers) != num_layers:
        raise ValueError("layers names %d layers, num_layers is %d"
                         % (len(layers), num_layers))
    data = sym.Variable('data')          # (batch, seq_len) int ids
    x = sym.Embedding(data=data, input_dim=num_classes,
                      output_dim=model_dim, name='embed')
    for i, kind in enumerate(layers):
        x = _block(x, num_heads, model_dim, ffn_dim, 'layer%d' % i,
                   num_kv_heads=num_kv_heads, use_flash=use_flash,
                   head_dim=head_dim, kind=kind, experts=experts)
    x = _norm(x, final_norm, model_dim, 'lnf')
    pred = sym.Reshape(data=x, shape=(-1, model_dim))
    return sym.FullyConnected(data=pred, num_hidden=num_classes,
                              no_bias=not head_bias, name='pred')


def get_symbol(num_classes=32000, seq_len=512, num_layers=4, num_heads=8,
               model_dim=512, ffn_dim=2048, num_kv_heads=0, use_flash=None,
               scalar_loss=False, head_dim=0, layers=None, experts=None,
               final_norm='layer', head_bias=True, **kwargs):
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
    always built: ``layers``, one dict a layer over ``LAYER_KINDS`` (norm,
    window, rope, rope_base, ffn), so that window + RoPE layers and global
    NoPE layers sit in one model; ``head_dim`` where it is not model_dim /
    num_heads; ``experts``, the ``ExpertFFN`` attributes (num_experts,
    experts_held, first_expert, top_k, ...) of the layers whose ffn is
    'experts', with ``ffn_dim`` one expert's width; ``final_norm``;
    ``head_bias`` False for a bias-free head. This is the only place the
    block is built for training: the decode builders
    (serving/generate/model.py) and the sharded step
    (parallel/transformer.py) build the dense LayerNorm block alone and say
    so when handed another."""
    pred = _backbone(num_classes, num_layers, num_heads, model_dim, ffn_dim,
                     num_kv_heads, use_flash, head_dim, layers, experts,
                     final_norm, head_bias)
    label = sym.Reshape(data=sym.Variable('softmax_label'), shape=(-1,))
    if scalar_loss:
        rows = sym.sum(sym.ones_like(sym.Cast(label, dtype='float32')))
        nll = sym._div(sym.softmax_cross_entropy(pred, label), rows)
        return sym.MakeLoss(nll, name='loss')
    return sym.SoftmaxOutput(data=pred, label=label, name='softmax')
