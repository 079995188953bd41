"""Decoder-only transformer LM on the Symbol API.

The framework's modern long-sequence model (SURVEY §5.7: the idiomatic
replacement for unrolled RNNs). Attention lowers to the Pallas flash kernel
on TPU (ops/attention.py → ops/pallas/flash_attention.py); the sharded
functional twin used for tp/pp/sp training lives in
mxnet_tpu.parallel.transformer.
"""
from .. import symbol as sym


class _Names:
    """Where a layer's nodes and leaves get their names. Alone (``shared``
    None) each leaf is named after its node and made where it is read, as
    the builder always did. In a loop ``shared`` holds the model's leaves,
    named after the layer (``leaf``) and made once, and the nodes are named
    after the pass (``node``): every pass reads the one set."""

    def __init__(self, node, leaf=None, shared=None):
        self.node = node
        self.leaf = node if leaf is None else leaf
        self.shared = shared

    def var(self, suffix, shape=None):
        name = self.leaf + suffix
        if self.shared is None:
            return sym.Variable(name, shape=shape)
        if name not in self.shared:
            self.shared[name] = sym.Variable(name, shape=shape)
        return self.shared[name]

    def given(self, suffix, *keys):
        """``{key: leaf}`` for the inputs an op would otherwise make itself
        (``<node>_<key>``): none alone, the shared leaves in a loop."""
        if self.shared is None:
            return {}
        return {k: self.var('%s_%s' % (suffix, k)) for k in keys}


def _norm(x, kind, dm, names, suffix, eps=None):
    """LayerNorm (gamma, beta) or RMSNorm (a plain scale) named
    ``<node><suffix>``; ``eps`` None leaves each op its own default (1e-5
    and 1e-6)."""
    gamma = names.var(suffix + '_gamma', (dm,))
    kw = {} if eps is None else {'eps': eps}
    name = names.node + suffix
    if kind == 'rms':
        return sym.RMSNorm(data=x, gamma=gamma, name=name, **kw)
    if kind != 'layer':
        raise ValueError("norm %r: 'layer' or 'rms'" % (kind,))
    beta = names.var(suffix + '_beta', (dm,))
    return sym.LayerNorm(data=x, gamma=gamma, beta=beta, name=name, **kw)


# What a layer may be; ``layers`` of get_symbol gives one such dict a layer
# (keys left out take these values, which are today's block).
LAYER_KINDS = {
    'norm': 'layer',      # 'layer' | 'rms'
    'mixer': 'attention',  # 'attention' | 'short_conv': ShortConv, which
                           # has no position encoding and no heads |
                           # 'latent': attention whose q, k and v come from
                           # low-rank latents (MLA: _latent_qkv)
    'conv_kernel': 3,     # taps a channel of a 'short_conv' mixer
    'q_rank': 0,          # a 'latent' mixer's query latent width
    'kv_rank': 0,         # a 'latent' mixer's key-value latent width
    'window': 0,          # keys a query sees, its own included; 0: all
    'rope': True,         # False: no position encoding at all (NoPE)
    'rope_base': 10000.0,
    'rope_dims': 0,       # RoPE over the trailing rope_dims of every q and
                          # k head (a 'latent' mixer's shared rotary key);
                          # 0: over the whole head
    'qk_norm': False,     # RMSNorm of every q and k head before the rotation
    'ffn': 'gelu',        # 'gelu': biased dense GELU | 'swiglu': dense
                          # gated, ffn2(silu(ffn1 x) * ffn3 x), no bias |
                          # 'experts': ExpertFFN
    'ffn_dim': 0,         # this layer's feed-forward width; 0: the model's
    'shared_dim': 0,      # beside 'experts': a shared expert, a dense
                          # SwiGLU of this width on the experts' input,
                          # added unweighted; 0: none
    'router_input': 'mixer',  # what an 'experts' router reads: the
                              # 'mixer' input's norm | the 'ffn' input's
    'post_norm': False,   # True: the mixer's and the feed-forward's outputs
                          # are normed too before each residual add (the
                          # sandwich): x + norm(mixer(norm x))
}


def _latent_qkv(h, num_heads, head_dim, names, kind, eps):
    """A 'latent' mixer's q, k and v over the normed input ``h`` (DeepSeek-V2's
    multi-head latent attention; ``r`` = ``rope_dims``): the query from the
    RMS-normed ``q_rank`` latent, ``q_b(norm(q_a h))``, a head's
    ``head_dim``; one kv down-projection ``kv_a h`` to the ``kv_rank``
    latent and ``r`` numbers more, the rotary key part every head shares;
    the RMS-normed latent's up-projection ``kv_b``, a head's unrotated key
    part (``head_dim - r``) and its value (``head_dim``), and ``LatentKV``
    assembling the key. No other key or value projection; no bias. The
    nodes are named ``<node>_mla_<part>``."""
    r, dq = kind['rope_dims'], num_heads * head_dim
    if not (kind['q_rank'] and kind['kv_rank'] and kind['rope']
            and 0 < r < head_dim):
        raise ValueError("a latent mixer takes q_rank, kv_rank and a rotary "
                         "part 0 < rope_dims < head_dim %d (got %r, %r, %r)"
                         % (head_dim, kind['q_rank'], kind['kv_rank'], r))
    part = names.node + '_mla_'

    def project(x, width, what):
        return sym.FullyConnected(data=x, num_hidden=width, flatten=False,
                                  no_bias=True, name=part + what,
                                  **names.given('_mla_' + what, 'weight'))

    q_latent = _norm(project(h, kind['q_rank'], 'q_a'), 'rms',
                     kind['q_rank'], names, '_mla_q_norm', eps)
    q = project(q_latent, dq, 'q_b')
    latent = project(h, kind['kv_rank'] + r, 'kv_a')
    kv_latent = _norm(sym.slice_axis(latent, axis=2, begin=0,
                                     end=kind['kv_rank'],
                                     name=part + 'kv_latent'),
                      'rms', kind['kv_rank'], names, '_mla_kv_norm', eps)
    kv = project(kv_latent, num_heads * (2 * head_dim - r), 'kv_b')
    key_value = sym.LatentKV(latent=latent, kv=kv, num_heads=num_heads,
                             head_dim=head_dim, rope_dims=r, name=part + 'kv')
    return q, key_value[0], key_value[1]


def _attention(h, num_heads, dm, names, num_kv_heads, use_flash, head_dim,
               kind, eps):
    """q, k, v, attention and o over the normed input ``h``."""
    # GQA (num_kv_heads < num_heads): k/v projections shrink to
    # num_kv_heads*head_dim and the flash kernel streams them narrow
    head_dim = head_dim or dm // num_heads
    dq = head_dim * num_heads
    dkv = dq if not num_kv_heads else head_dim * num_kv_heads
    name = names.node
    if kind['mixer'] == 'latent':
        if num_kv_heads not in (0, num_heads):
            raise ValueError("a latent mixer gives every head its own key "
                             "and value: num_kv_heads %d of %d heads"
                             % (num_kv_heads, num_heads))
        q, k, v = _latent_qkv(h, num_heads, head_dim, names, kind, eps)
    else:
        q = sym.FullyConnected(data=h, num_hidden=dq, flatten=False,
                               no_bias=True, name=name + '_q',
                               **names.given('_q', 'weight'))
        k = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                               no_bias=True, name=name + '_k',
                               **names.given('_k', 'weight'))
        v = sym.FullyConnected(data=h, num_hidden=dkv, flatten=False,
                               no_bias=True, name=name + '_v',
                               **names.given('_v', 'weight'))
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
    if kind['rope'] and kind['rope_dims']:
        att_kw['rope_dims'] = kind['rope_dims']
    if kind['qk_norm']:
        att_kw['qk_norm'] = True
        if eps is not None:
            att_kw['qk_norm_eps'] = eps
        att_kw.update(names.given('_attn', 'q_norm_gamma', 'k_norm_gamma'))
    att = sym.MultiHeadAttention(query=q, key=k, value=v, num_heads=num_heads,
                                 num_kv_heads=num_kv_heads, causal=True,
                                 use_rope=bool(kind['rope']),
                                 name=name + '_attn', **att_kw)
    return sym.FullyConnected(data=att, num_hidden=dm, flatten=False,
                              no_bias=True, name=name + '_o',
                              **names.given('_o', 'weight'))


def _swiglu(h, dff, dm, names, part=''):
    """The dense gated feed-forward, ``ffn2(silu(ffn1 h) * ffn3 h)``, no
    bias, its nodes named ``<node><part>_ffn1`` and so on."""
    name = names.node + part
    gate = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                              no_bias=True, name=name + '_ffn1',
                              **names.given(part + '_ffn1', 'weight'))
    up = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                            no_bias=True, name=name + '_ffn3',
                            **names.given(part + '_ffn3', 'weight'))
    h = sym.broadcast_mul(
        sym.Activation(data=gate, act_type='silu', name=name + '_silu'),
        up, name=name + '_glu')
    return sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                              no_bias=True, name=name + '_ffn2',
                              **names.given(part + '_ffn2', 'weight'))


def _block(x, num_heads, dm, dff, names, num_kv_heads=0, use_flash=None,
           head_dim=0, kind=LAYER_KINDS, experts=None, eps=None):
    """One pre-norm decoder block of the kinds ``kind`` names:
    ``x + mixer(norm1 x)``, then ``+ ffn(norm2 ...)`` (with ``post_norm``,
    each branch's output normed before its add). ``names``: a ``_Names``.
    ``experts`` (for ffn 'experts'): the ExpertFFN attributes, and the
    layer's width is then one expert's."""
    unknown = set(kind) - set(LAYER_KINDS)
    if unknown:
        raise ValueError("layer kinds %s unknown (known: %s)"
                         % (sorted(unknown), sorted(LAYER_KINDS)))
    kind = dict(LAYER_KINDS, **kind)
    if kind['shared_dim'] and kind['ffn'] != 'experts':
        raise ValueError("shared_dim: a shared expert sits beside 'experts'")
    dff = kind['ffn_dim'] or dff
    name = names.node

    def add(x, out, suffix):
        if kind['post_norm']:
            out = _norm(out, kind['norm'], dm, names, suffix, eps)
        return x + out

    h = _norm(x, kind['norm'], dm, names, '_ln1', eps)
    mixer_in = h
    if kind['mixer'] == 'short_conv':
        taps = kind['conv_kernel']
        mixed = sym.ShortConv(
            data=h, kernel=taps,
            in_weight=names.var('_conv_in_weight', (3 * dm, dm)),
            conv_weight=names.var('_conv_weight', (dm, taps)),
            out_weight=names.var('_conv_out_weight', (dm, dm)),
            name=name + '_conv')
    elif kind['mixer'] in ('attention', 'latent'):
        mixed = _attention(h, num_heads, dm, names, num_kv_heads, use_flash,
                           head_dim, kind, eps)
    else:
        raise ValueError("mixer %r: 'attention', 'latent' or 'short_conv'"
                         % (kind['mixer'],))
    x = add(x, mixed, '_post1')
    h = _norm(x, kind['norm'], dm, names, '_ln2', eps)
    if kind['ffn'] == 'experts':
        if kind['router_input'] not in ('mixer', 'ffn'):
            raise ValueError("router_input %r: 'mixer' or 'ffn'"
                             % (kind['router_input'],))
        held = experts.get('experts_held') or experts['num_experts']
        out = sym.ExpertFFN(
            data=h,
            router_data=mixer_in if kind['router_input'] == 'mixer' else h,
            router_weight=names.var('_router_weight',
                                    (experts['num_experts'], dm)),
            gate_weight=names.var('_gate_weight', (held, dff, dm)),
            up_weight=names.var('_up_weight', (held, dff, dm)),
            down_weight=names.var('_down_weight', (held, dm, dff)),
            name=name + '_experts', **experts)[0]
        if kind['shared_dim']:
            out = out + _swiglu(h, kind['shared_dim'], dm, names, '_shared')
        return add(x, out, '_post2')
    if kind['ffn'] == 'swiglu':
        return add(x, _swiglu(h, dff, dm, names), '_post2')
    if kind['ffn'] != 'gelu':
        raise ValueError("ffn %r: 'gelu', 'swiglu' or 'experts'"
                         % (kind['ffn'],))
    h = sym.FullyConnected(data=h, num_hidden=dff, flatten=False,
                           name=name + '_ffn1',
                           **names.given('_ffn1', 'weight', 'bias'))
    h = sym.Activation(data=h, act_type='gelu', name=name + '_gelu')
    h = sym.FullyConnected(data=h, num_hidden=dm, flatten=False,
                           name=name + '_ffn2',
                           **names.given('_ffn2', 'weight', 'bias'))
    return add(x, h, '_post2')


def _backbone(num_classes, num_layers, num_heads, model_dim, ffn_dim,
              num_kv_heads, use_flash, head_dim=0, layers=None, experts=None,
              final_norm='layer', head_bias=True, norm_eps=None,
              tie_head=False, loops=1, ends=None):
    """The exits: one ``(logits, gate logit)`` a pass, the last pass's gate
    None (alone: the one pass's logits and no gate). ``ends``: a dict that
    receives what a multi-token-prediction module shares with the model
    (one pass): the table (``table``), the head's leaves (``head``) and the
    state before the final norm (``state``); the two ends' leaves are then
    made here, under the names their ops would give them."""
    if layers is None:
        layers = [LAYER_KINDS] * num_layers
    if len(layers) != num_layers:
        raise ValueError("layers names %d layers, num_layers is %d"
                         % (len(layers), num_layers))
    if tie_head and head_bias:
        raise ValueError("tie_head: the table has no bias to share "
                         "(head_bias=False)")
    if loops > 1 and experts and experts.get('route') == 'sigmoid_bias':
        raise ValueError("loops: an expert layer's expert_bias state is "
                         "not shared by the passes")
    data = sym.Variable('data')          # (batch, seq_len) int ids
    tied = {'weight': sym.Variable('embed_weight',
                                   shape=(num_classes, model_dim))} \
        if tie_head or ends is not None else {}
    x = sym.Embedding(data=data, input_dim=num_classes,
                      output_dim=model_dim, name='embed', **tied)
    ends_head = {}
    if ends is not None:
        ends_head = tied if tie_head else {'weight': sym.Variable(
            'pred_weight', shape=(num_classes, model_dim))}
        ends.update(table=tied['weight'], head=ends_head)
    shared = None if loops == 1 else {}
    exits = []
    for t in range(loops):
        # a pass's nodes are named for it, its leaves for the layer alone
        at = '' if loops == 1 else 'ut%d_' % t
        for i, kind in enumerate(layers):
            x = _block(x, num_heads, model_dim, ffn_dim,
                       _Names(at + 'layer%d' % i, 'layer%d' % i, shared),
                       num_kv_heads=num_kv_heads, use_flash=use_flash,
                       head_dim=head_dim, kind=kind, experts=experts,
                       eps=norm_eps)
        if ends is not None:
            ends['state'] = x
        # the normed state is both this pass's exit and the next pass's
        # input
        top = _Names(at, '', shared)
        x = _norm(x, final_norm, model_dim, top, 'lnf', norm_eps)
        flat = {} if loops == 1 else {'name': at + 'flat'}
        pred = sym.Reshape(data=x, shape=(-1, model_dim), **flat)
        # a tied head multiplies by the table itself: one leaf, whose
        # gradient is the sum of both uses (and so is a looped leaf's)
        head = ends_head or tied or top.given(
            'pred', 'weight', *(('bias',) if head_bias else ()))
        logits = sym.FullyConnected(data=pred, num_hidden=num_classes,
                                    no_bias=not head_bias,
                                    name=at + 'pred', **head)
        gate = None
        if t < loops - 1:  # the last exit takes what the others leave
            gate = sym.FullyConnected(
                data=pred, num_hidden=1, name=at + 'exit_gate',
                **top.given('exit_gate', 'weight', 'bias'))
        exits.append((logits, gate))
    return exits


def get_symbol(num_classes=32000, seq_len=512, num_layers=4, num_heads=8,
               model_dim=512, ffn_dim=2048, num_kv_heads=0, use_flash=None,
               scalar_loss=False, head_dim=0, layers=None, experts=None,
               final_norm='layer', head_bias=True, norm_eps=None,
               tie_head=False, loops=1, exit_loss=None, mtp=None, **kwargs):
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
    and with ``post_norm`` the sandwich; the mixer: attention with its
    window, rope, rope_base, rope_dims and qk_norm, latent attention with
    its ranks, or a gated short convolution; the feed-forward: biased GELU,
    dense gated SwiGLU or experts with a shared expert or none, and its
    width where a layer's differs), so that window + RoPE layers, global NoPE
    layers and convolution layers sit in one model; ``head_dim`` where it
    is not model_dim / num_heads; ``experts``, the ``ExpertFFN`` attributes
    (num_experts, experts_held, first_expert, top_k, route, ...) of the
    layers whose ffn is 'experts', with ``ffn_dim`` one expert's width;
    ``final_norm``; ``norm_eps`` for every norm of the model (None: each
    op's default); ``head_bias`` False for a bias-free head, ``tie_head``
    for one that multiplies by the embedding table.

    ``loops`` > 1 runs the layer stack that many times with one set of
    leaves (each read by every pass; the nodes named ``ut<t>_...`` for the
    pass). Every pass ends in the final norm, whose output is the next
    pass's input and the pass's exit: the head and, but for the last, an
    exit gate (``exit_gate``, hidden -> 1), all shared by the exits. The
    loss is then ``exit_loss`` (``{'beta': ...}``, required): the
    ``LoopExitLoss`` objective over the exits, summed over the rows and
    divided by their number, behind ``MakeLoss``.

    ``mtp`` (``{'layer': kind, 'weight': w}``, with ``scalar_loss``, one
    pass and ``head_bias=False``) adds one multi-token-prediction module
    (``_mtp_loss``) that shares the table and the head: the loss is then
    the next-token mean plus ``w`` times the module's mean over the
    positions that have a token two ahead.

    This is the only place the block is built for training: the decode
    builders (serving/generate/model.py) and the sharded step
    (parallel/transformer.py) build the dense LayerNorm block alone, one
    pass, and say so when handed another."""
    if (loops > 1) != (exit_loss is not None):
        raise ValueError("loops %d with exit_loss %r: a loop trains on the "
                         "exits' objective, and only a loop has exits"
                         % (loops, exit_loss))
    if mtp is not None and (loops > 1 or not scalar_loss or head_bias):
        raise ValueError("mtp: a module beside one pass, trained on the "
                         "scalar loss, sharing a head without a bias")
    ends = None if mtp is None else {}
    exits = _backbone(num_classes, num_layers, num_heads, model_dim,
                      ffn_dim, num_kv_heads, use_flash, head_dim, layers,
                      experts, final_norm, head_bias, norm_eps, tie_head,
                      loops, ends)
    labels = sym.Variable('softmax_label')
    label = sym.Reshape(data=labels, shape=(-1,))
    if exit_loss is not None:
        rows = sym.sum(sym.ones_like(sym.Cast(label, dtype='float32')))
        total = sym.LoopExitLoss(
            *[z for z, _ in exits], *[g for _, g in exits[:-1]], label,
            num_exits=loops, beta=float(exit_loss['beta']),
            name='exit_loss')
        return sym.MakeLoss(sym._div(total, rows), name='loss')
    (pred, _), = exits
    if scalar_loss:
        rows = sym.sum(sym.ones_like(sym.Cast(label, dtype='float32')))
        nll = sym._div(sym.softmax_cross_entropy(pred, label), rows)
        if mtp is not None:
            nll = nll + _mtp_loss(
                ends, labels, mtp, num_classes, num_heads, model_dim,
                ffn_dim, num_kv_heads, use_flash, head_dim, experts,
                final_norm, norm_eps)
        return sym.MakeLoss(nll, name='loss')
    return sym.SoftmaxOutput(data=pred, label=label, name='softmax')


def _mtp_loss(ends, labels, mtp, num_classes, num_heads, dm, dff,
              num_kv_heads, use_flash, head_dim, experts, final_norm, eps):
    """One multi-token-prediction module (DeepSeek-V3's) and its weighted
    objective: position i's state before the final norm, ``h_i``, and the
    embedding of its label (the token after i) each normed and joined,
    ``eh_proj [norm_e(E[t_{i+1}]); norm_h(h_i)]``, one block of
    ``mtp['layer']``'s kind causal over the sequence, a norm of its own and
    the model's head; the target is the label one position later
    (``MultiTokenLoss``). Table and head are the model's own leaves; the
    module's nodes are named ``mtp_...``."""
    names = _Names('mtp')
    embedded = sym.Embedding(data=labels, input_dim=num_classes,
                             output_dim=dm, weight=ends['table'],
                             name='mtp_embed')
    joined = sym.Concat(_norm(embedded, final_norm, dm, names, '_enorm', eps),
                        _norm(ends['state'], final_norm, dm, names, '_hnorm',
                              eps), dim=2, name='mtp_concat')
    x = sym.FullyConnected(data=joined, num_hidden=dm, flatten=False,
                           no_bias=True, name='mtp_eh_proj')
    x = _block(x, num_heads, dm, dff, _Names('mtp_layer'),
               num_kv_heads=num_kv_heads, use_flash=use_flash,
               head_dim=head_dim, kind=mtp['layer'], experts=experts, eps=eps)
    x = sym.Reshape(data=_norm(x, final_norm, dm, names, '_lnf', eps),
                    shape=(-1, dm), name='mtp_flat')
    logits = sym.FullyConnected(data=x, num_hidden=num_classes, no_bias=True,
                                name='mtp_pred', **ends['head'])
    return sym.MultiTokenLoss(logits, labels, weight=float(mtp['weight']),
                              name='mtp_loss')
