"""Decoder-only transformer LM on the Symbol API.

The framework's modern long-sequence model (SURVEY §5.7: the idiomatic
replacement for unrolled RNNs). Attention lowers to the Pallas flash kernel
on TPU (ops/attention.py → ops/pallas/flash_attention.py); the sharded
functional twin used for tp/pp/sp training lives in
mxnet_tpu.parallel.transformer.
"""
from .. import symbol as sym


def _block(x, num_heads, dm, dff, name, num_kv_heads=0, use_flash=None):
    ln1_g = sym.Variable(name + '_ln1_gamma', shape=(dm,))
    ln1_b = sym.Variable(name + '_ln1_beta', shape=(dm,))
    h = sym.LayerNorm(data=x, gamma=ln1_g, beta=ln1_b, name=name + '_ln1')
    # GQA (num_kv_heads < num_heads): k/v projections shrink to
    # num_kv_heads*head_dim and the flash kernel streams them narrow
    dkv = dm if not num_kv_heads else dm // num_heads * num_kv_heads
    q = sym.FullyConnected(data=h, num_hidden=dm, flatten=False, no_bias=True,
                           name=name + '_q')
    k = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                           no_bias=True, name=name + '_k')
    v = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                           no_bias=True, name=name + '_v')
    # use_flash=None defers to the op default (True, with the kernel's
    # own on-TPU/shape selection gate) — passing None through would
    # read as falsy and silently pin the einsum path
    flash_kw = {} if use_flash is None else {'use_flash': use_flash}
    att = sym.MultiHeadAttention(query=q, key=k, value=v, num_heads=num_heads,
                                 num_kv_heads=num_kv_heads, causal=True,
                                 use_rope=True, name=name + '_attn',
                                 **flash_kw)
    att = sym.FullyConnected(data=att, num_hidden=dm, flatten=False,
                             no_bias=True, name=name + '_o')
    x = x + att
    ln2_g = sym.Variable(name + '_ln2_gamma', shape=(dm,))
    ln2_b = sym.Variable(name + '_ln2_beta', shape=(dm,))
    h = sym.LayerNorm(data=x, gamma=ln2_g, beta=ln2_b, name=name + '_ln2')
    h = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                           name=name + '_ffn1')
    h = sym.Activation(data=h, act_type='gelu', name=name + '_gelu')
    h = sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                           name=name + '_ffn2')
    return x + h


def _backbone(num_classes, num_layers, num_heads, model_dim, ffn_dim,
              num_kv_heads, use_flash):
    data = sym.Variable('data')          # (batch, seq_len) int ids
    x = sym.Embedding(data=data, input_dim=num_classes,
                      output_dim=model_dim, name='embed')
    for i in range(num_layers):
        x = _block(x, num_heads, model_dim, ffn_dim, 'layer%d' % i,
                   num_kv_heads=num_kv_heads, use_flash=use_flash)
    lnf_g = sym.Variable('lnf_gamma', shape=(model_dim,))
    lnf_b = sym.Variable('lnf_beta', shape=(model_dim,))
    x = sym.LayerNorm(data=x, gamma=lnf_g, beta=lnf_b, name='lnf')
    pred = sym.Reshape(data=x, shape=(-1, model_dim))
    return sym.FullyConnected(data=pred, num_hidden=num_classes, name='pred')


def get_symbol(num_classes=32000, seq_len=512, num_layers=4, num_heads=8,
               model_dim=512, ffn_dim=2048, num_kv_heads=0, use_flash=None,
               scalar_loss=False, **kwargs):
    """Decoder LM symbol. scalar_loss=True emits a MakeLoss mean-NLL head
    (output ``loss``) instead of SoftmaxOutput — the (batch*seq, vocab)
    probability output is the right inference surface but costs a fresh
    device buffer per step, which benchmark/training loops that only need
    the loss avoid (docs/perf.md LSTM caveat). The head is
    ``softmax_cross_entropy`` (the closed form: a float32 logsumexp over
    the vocabulary less the label's logit, and a backward that builds no
    one-hot) over the number of rows, which is counted in float32 from the
    label's shape and folds to a constant."""
    pred = _backbone(num_classes, num_layers, num_heads, model_dim, ffn_dim,
                     num_kv_heads, use_flash)
    label = sym.Reshape(data=sym.Variable('softmax_label'), shape=(-1,))
    if scalar_loss:
        rows = sym.sum(sym.ones_like(sym.Cast(label, dtype='float32')))
        nll = sym._div(sym.softmax_cross_entropy(pred, label), rows)
        return sym.MakeLoss(nll, name='loss')
    return sym.SoftmaxOutput(data=pred, label=label, name='softmax')
