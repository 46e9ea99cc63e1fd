/* fastcodec — CPython extension interpreting rpc.codec's wire format.
 *
 * The Python codec (pegasus_tpu/rpc/codec.py) derives encoder/decoder
 * closures from dataclass annotations; profiling the serving path showed
 * ~half the server CPU inside those closures (varints, per-byte bytearray
 * appends, getattr walks). This module executes the SAME wire format from
 * a compact node tree compiled once per dataclass by codec._fast_plan:
 *
 *   int        -> zigzag varint            node 'i'
 *   bool       -> 1 byte                   node 'b'
 *   bytes      -> varint length + raw      node 'y'
 *   str        -> varint length + utf-8    node 's'
 *   IntEnum    -> as int (decode rewraps)  node 'e' (py = enum class)
 *   Optional   -> presence byte + inner    node 'O'
 *   List       -> varint count + items     node 'L'
 *   dataclass  -> varint field count + fields in order   node 'D' (py = Plan)
 *   unsupported-> lazily illegal (empty List / None Optional still fine)
 *                                          node 'X'
 *
 * Byte-for-byte identical to the Python codec (differentially fuzzed by
 * tests/test_fastcodec.py). Ints support the full range the Python
 * encoder produces for this codebase: [-2^63, 2^64) via __int128 zigzag
 * (partition hashes are unsigned 64-bit).
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <errno.h>
#include <stdint.h>
#include <string.h>
#include <sys/socket.h>
#include <sys/uio.h>

#ifndef MSG_NOSIGNAL
#define MSG_NOSIGNAL 0
#endif

static PyObject *CodecError; /* set by register_error(); fallback ValueError */

#define RAISE(msg)                                                         \
    do {                                                                   \
        PyErr_SetString(CodecError ? CodecError : PyExc_ValueError, msg);  \
    } while (0)

/* ------------------------------------------------------------------ nodes */

typedef struct Node {
    char kind;
    struct Node *inner; /* O, L */
    PyObject *py;       /* D: Plan (strong), e: enum class (strong) */
} Node;

static void node_free(Node *n)
{
    if (!n)
        return;
    node_free(n->inner);
    Py_XDECREF(n->py);
    PyMem_Free(n);
}

/* ------------------------------------------------------------------- plan */

typedef struct {
    PyObject_HEAD
    PyObject *cls;   /* dataclass constructor */
    PyObject *names; /* tuple of str (interned) */
    Py_ssize_t nfields;
    Node **nodes; /* array[nfields] */
    int ready;
} PlanObject;

static PyTypeObject Plan_Type; /* fwd */

static Node *parse_spec(PyObject *spec)
{
    if (!PyTuple_Check(spec) || PyTuple_GET_SIZE(spec) < 1) {
        RAISE("spec must be a non-empty tuple");
        return NULL;
    }
    PyObject *k = PyTuple_GET_ITEM(spec, 0);
    const char *ks = PyUnicode_AsUTF8(k);
    if (!ks)
        return NULL;
    Node *n = PyMem_Calloc(1, sizeof(Node));
    if (!n) {
        PyErr_NoMemory();
        return NULL;
    }
    n->kind = ks[0];
    switch (n->kind) {
    case 'i':
    case 'b':
    case 'y':
    case 's':
    case 'X':
        return n;
    case 'e':
    case 'D': {
        if (PyTuple_GET_SIZE(spec) != 2)
            goto bad;
        PyObject *payload = PyTuple_GET_ITEM(spec, 1);
        if (n->kind == 'D' && !PyObject_TypeCheck(payload, &Plan_Type))
            goto bad;
        Py_INCREF(payload);
        n->py = payload;
        return n;
    }
    case 'O':
    case 'L': {
        if (PyTuple_GET_SIZE(spec) != 2)
            goto bad;
        n->inner = parse_spec(PyTuple_GET_ITEM(spec, 1));
        if (!n->inner) {
            PyMem_Free(n);
            return NULL;
        }
        return n;
    }
    default:
        goto bad;
    }
bad:
    PyMem_Free(n);
    RAISE("malformed spec");
    return NULL;
}

/* ----------------------------------------------------------------- buffer */

typedef struct {
    unsigned char *p;
    Py_ssize_t len, cap;
} Buf;

static int buf_grow(Buf *b, Py_ssize_t extra)
{
    Py_ssize_t need = b->len + extra;
    if (need <= b->cap)
        return 0;
    Py_ssize_t cap = b->cap ? b->cap : 256;
    while (cap < need)
        cap <<= 1;
    unsigned char *np = PyMem_Realloc(b->p, cap);
    if (!np) {
        PyErr_NoMemory();
        return -1;
    }
    b->p = np;
    b->cap = cap;
    return 0;
}

static inline int buf_byte(Buf *b, unsigned char c)
{
    if (b->len >= b->cap && buf_grow(b, 1) < 0)
        return -1;
    b->p[b->len++] = c;
    return 0;
}

static int buf_varint(Buf *b, unsigned __int128 v)
{
    if (buf_grow(b, 19) < 0) /* 128/7 rounded up */
        return -1;
    while (v >= 0x80) {
        b->p[b->len++] = (unsigned char)(v & 0x7F) | 0x80;
        v >>= 7;
    }
    b->p[b->len++] = (unsigned char)v;
    return 0;
}

static int buf_raw(Buf *b, const char *src, Py_ssize_t n)
{
    if (buf_grow(b, n) < 0)
        return -1;
    memcpy(b->p + b->len, src, n);
    b->len += n;
    return 0;
}

/* ----------------------------------------------------------------- encode */

static int enc_value(Node *n, PyObject *v, Buf *b);

static int enc_int_obj(PyObject *v, Buf *b)
{
    int ovf = 0;
    long long sv = PyLong_AsLongLongAndOverflow(v, &ovf);
    if (sv == -1 && !ovf && PyErr_Occurred())
        return -1;
    unsigned __int128 z;
    if (!ovf) {
        __int128 w = (__int128)sv;
        z = (unsigned __int128)((w << 1) ^ (w >> 63));
    } else if (ovf > 0) {
        unsigned long long uv = PyLong_AsUnsignedLongLong(v);
        if (uv == (unsigned long long)-1 && PyErr_Occurred())
            return -1;
        z = ((unsigned __int128)uv) << 1;
    } else {
        RAISE("int below -2^63 unsupported");
        return -1;
    }
    return buf_varint(b, z);
}

static int enc_struct(PlanObject *p, PyObject *obj, Buf *b)
{
    if (!p->ready) { /* a nested plan must never be an in-flight shell */
        RAISE("plan not initialized");
        return -1;
    }
    if (buf_byte(b, (unsigned char)p->nfields) < 0)
        return -1;
    for (Py_ssize_t i = 0; i < p->nfields; i++) {
        PyObject *v = PyObject_GetAttr(obj, PyTuple_GET_ITEM(p->names, i));
        if (!v)
            return -1;
        int rc = enc_value(p->nodes[i], v, b);
        Py_DECREF(v);
        if (rc < 0)
            return -1;
    }
    return 0;
}

static int enc_value(Node *n, PyObject *v, Buf *b)
{
    switch (n->kind) {
    case 'i':
    case 'e': { /* enums encode as their int value */
        if (PyLong_CheckExact(v))
            return enc_int_obj(v, b);
        PyObject *iv = PyNumber_Index(v);
        if (!iv)
            return -1;
        int rc = enc_int_obj(iv, b);
        Py_DECREF(iv);
        return rc;
    }
    case 'b':
    {
        int t = PyObject_IsTrue(v);
        if (t < 0)
            return -1;
        return buf_byte(b, t ? 1 : 0);
    }
    case 'y': {
        if (PyBytes_Check(v)) {
            Py_ssize_t ln = PyBytes_GET_SIZE(v);
            if (buf_varint(b, (unsigned __int128)ln) < 0)
                return -1;
            return buf_raw(b, PyBytes_AS_STRING(v), ln);
        }
        Py_buffer view;
        if (PyObject_GetBuffer(v, &view, PyBUF_SIMPLE) < 0)
            return -1;
        int rc = buf_varint(b, (unsigned __int128)view.len);
        if (rc == 0)
            rc = buf_raw(b, view.buf, view.len);
        PyBuffer_Release(&view);
        return rc;
    }
    case 's': {
        Py_ssize_t ln;
        const char *u = PyUnicode_AsUTF8AndSize(v, &ln);
        if (!u)
            return -1;
        if (buf_varint(b, (unsigned __int128)ln) < 0)
            return -1;
        return buf_raw(b, u, ln);
    }
    case 'O':
        if (v == Py_None)
            return buf_byte(b, 0);
        if (buf_byte(b, 1) < 0)
            return -1;
        return enc_value(n->inner, v, b);
    case 'L': {
        PyObject *fast = PySequence_Fast(v, "list field expects a sequence");
        if (!fast)
            return -1;
        Py_ssize_t cnt = PySequence_Fast_GET_SIZE(fast);
        if (buf_varint(b, (unsigned __int128)cnt) < 0) {
            Py_DECREF(fast);
            return -1;
        }
        PyObject **items = PySequence_Fast_ITEMS(fast);
        for (Py_ssize_t i = 0; i < cnt; i++) {
            if (enc_value(n->inner, items[i], b) < 0) {
                Py_DECREF(fast);
                return -1;
            }
        }
        Py_DECREF(fast);
        return 0;
    }
    case 'D':
        return enc_struct((PlanObject *)n->py, v, b);
    case 'X':
        RAISE("unsupported field type used with a non-empty value");
        return -1;
    }
    RAISE("corrupt plan");
    return -1;
}

/* ----------------------------------------------------------------- decode */

typedef struct {
    const unsigned char *p;
    Py_ssize_t len, off;
} Rd;

static PyObject *dec_value(Node *n, Rd *r);

static int rd_varint(Rd *r, unsigned __int128 *out)
{
    if (r->off >= r->len) {
        RAISE("truncated varint");
        return -1;
    }
    unsigned char b0 = r->p[r->off];
    if (!(b0 & 0x80)) { /* 1-byte fast path */
        r->off++;
        *out = b0;
        return 0;
    }
    unsigned __int128 val = 0;
    int shift = 0;
    for (;;) {
        if (r->off >= r->len) {
            RAISE("truncated varint");
            return -1;
        }
        unsigned char b = r->p[r->off++];
        val |= ((unsigned __int128)(b & 0x7F)) << shift;
        if (!(b & 0x80))
            break;
        shift += 7;
        if (shift > 63) {
            /* 10 bytes (shifts 0..63) cover every value the encoder can
             * emit for [-2^63, 2^64); a longer varint is corrupt input,
             * and continuing would shift continuation bits off the
             * 128-bit accumulator into a silently-wrong small value.
             * Matching the Python decoder's 10-byte bound, both paths
             * raise on the same malformed frames. */
            RAISE("varint overflow");
            return -1;
        }
    }
    *out = val;
    return 0;
}

static PyObject *dec_int(Rd *r)
{
    unsigned __int128 z;
    if (rd_varint(r, &z) < 0)
        return NULL;
    __int128 res = (__int128)(z >> 1) * ((z & 1) ? -1 : 1) - (__int128)(z & 1);
    /* equivalent to (z >> 1) ^ -(z & 1) in arbitrary precision */
    if (res >= 0) {
        if (res <= (__int128)UINT64_MAX)
            return PyLong_FromUnsignedLongLong((unsigned long long)res);
    } else if (res >= (__int128)INT64_MIN) {
        return PyLong_FromLongLong((long long)res);
    }
    RAISE("int out of range");
    return NULL;
}

static PyObject *dec_struct(PlanObject *p, Rd *r)
{
    if (!p->ready) { /* a nested plan must never be an in-flight shell */
        RAISE("plan not initialized");
        return NULL;
    }
    unsigned __int128 n128;
    if (rd_varint(r, &n128) < 0)
        return NULL;
    Py_ssize_t n = (Py_ssize_t)n128;
    if (n > p->nfields) {
        PyErr_Format(CodecError ? CodecError : PyExc_ValueError,
                     "%s: encoder sent %zd fields, decoder knows %zd",
                     ((PyTypeObject *)p->cls)->tp_name, n, p->nfields);
        return NULL;
    }
    PyObject *args[128];
    Py_ssize_t got = 0;
    for (; got < n; got++) {
        args[got] = dec_value(p->nodes[got], r);
        if (!args[got])
            goto fail;
    }
    PyObject *obj = PyObject_Vectorcall(p->cls, args, (size_t)n, NULL);
    for (Py_ssize_t i = 0; i < got; i++)
        Py_DECREF(args[i]);
    return obj;
fail:
    for (Py_ssize_t i = 0; i < got; i++)
        Py_DECREF(args[i]);
    return NULL;
}

static PyObject *dec_value(Node *n, Rd *r)
{
    switch (n->kind) {
    case 'i':
        return dec_int(r);
    case 'e': {
        PyObject *iv = dec_int(r);
        if (!iv)
            return NULL;
        PyObject *ev = PyObject_CallOneArg(n->py, iv);
        Py_DECREF(iv);
        return ev;
    }
    case 'b': {
        if (r->off >= r->len) {
            RAISE("truncated bool");
            return NULL;
        }
        PyObject *v = r->p[r->off++] ? Py_True : Py_False;
        Py_INCREF(v);
        return v;
    }
    case 'y': {
        unsigned __int128 ln;
        if (rd_varint(r, &ln) < 0)
            return NULL;
        if (ln > (unsigned __int128)(r->len - r->off)) {
            RAISE("truncated bytes");
            return NULL;
        }
        PyObject *v = PyBytes_FromStringAndSize(
            (const char *)r->p + r->off, (Py_ssize_t)ln);
        r->off += (Py_ssize_t)ln;
        return v;
    }
    case 's': {
        unsigned __int128 ln;
        if (rd_varint(r, &ln) < 0)
            return NULL;
        if (ln > (unsigned __int128)(r->len - r->off)) {
            RAISE("truncated str");
            return NULL;
        }
        PyObject *v = PyUnicode_DecodeUTF8(
            (const char *)r->p + r->off, (Py_ssize_t)ln, NULL);
        r->off += (Py_ssize_t)ln;
        return v;
    }
    case 'O': {
        if (r->off >= r->len) {
            RAISE("truncated optional");
            return NULL;
        }
        unsigned char flag = r->p[r->off++];
        if (!flag)
            Py_RETURN_NONE;
        return dec_value(n->inner, r);
    }
    case 'L': {
        unsigned __int128 cnt128;
        if (rd_varint(r, &cnt128) < 0)
            return NULL;
        if (cnt128 > (unsigned __int128)(r->len - r->off)) {
            RAISE("truncated list"); /* every item needs >= 1 byte */
            return NULL;
        }
        Py_ssize_t cnt = (Py_ssize_t)cnt128;
        PyObject *lst = PyList_New(cnt);
        if (!lst)
            return NULL;
        for (Py_ssize_t i = 0; i < cnt; i++) {
            PyObject *item = dec_value(n->inner, r);
            if (!item) {
                Py_DECREF(lst);
                return NULL;
            }
            PyList_SET_ITEM(lst, i, item);
        }
        return lst;
    }
    case 'D':
        return dec_struct((PlanObject *)n->py, r);
    case 'X':
        RAISE("unsupported field type present on the wire");
        return NULL;
    }
    RAISE("corrupt plan");
    return NULL;
}

/* ------------------------------------------------------------ Plan object */

static PyObject *Plan_new(PyTypeObject *type, PyObject *args, PyObject *kw)
{
    PlanObject *self = (PlanObject *)type->tp_alloc(type, 0);
    if (self) {
        self->cls = NULL;
        self->names = NULL;
        self->nodes = NULL;
        self->nfields = 0;
        self->ready = 0;
    }
    return (PyObject *)self;
}

static void Plan_dealloc(PlanObject *self)
{
    for (Py_ssize_t i = 0; i < self->nfields; i++)
        node_free(self->nodes ? self->nodes[i] : NULL);
    PyMem_Free(self->nodes);
    Py_XDECREF(self->cls);
    Py_XDECREF(self->names);
    Py_TYPE(self)->tp_free((PyObject *)self);
}

static PyObject *Plan_init_plan(PlanObject *self, PyObject *args)
{
    PyObject *cls, *names, *specs;
    if (!PyArg_ParseTuple(args, "OO!O!", &cls, &PyTuple_Type, &names,
                          &PyTuple_Type, &specs))
        return NULL;
    if (self->ready) {
        RAISE("plan already initialized");
        return NULL;
    }
    Py_ssize_t n = PyTuple_GET_SIZE(names);
    if (n != PyTuple_GET_SIZE(specs) || n >= 128) {
        RAISE("names/specs mismatch or too many fields");
        return NULL;
    }
    self->nodes = PyMem_Calloc(n, sizeof(Node *));
    if (!self->nodes)
        return PyErr_NoMemory();
    for (Py_ssize_t i = 0; i < n; i++) {
        self->nodes[i] = parse_spec(PyTuple_GET_ITEM(specs, i));
        if (!self->nodes[i]) {
            for (Py_ssize_t j = 0; j < i; j++)
                node_free(self->nodes[j]);
            PyMem_Free(self->nodes);
            self->nodes = NULL;
            return NULL;
        }
    }
    Py_INCREF(cls);
    self->cls = cls;
    Py_INCREF(names);
    self->names = names;
    self->nfields = n;
    self->ready = 1;
    Py_RETURN_NONE;
}

static PyObject *Plan_encode(PlanObject *self, PyObject *obj)
{
    if (!self->ready) {
        RAISE("plan not initialized");
        return NULL;
    }
    Buf b = {NULL, 0, 0};
    if (enc_struct(self, obj, &b) < 0) {
        PyMem_Free(b.p);
        return NULL;
    }
    PyObject *out = PyBytes_FromStringAndSize((const char *)b.p, b.len);
    PyMem_Free(b.p);
    return out;
}

static PyObject *Plan_decode(PlanObject *self, PyObject *data)
{
    if (!self->ready) {
        RAISE("plan not initialized");
        return NULL;
    }
    Py_buffer view;
    if (PyObject_GetBuffer(data, &view, PyBUF_SIMPLE) < 0)
        return NULL;
    Rd r = {view.buf, view.len, 0};
    PyObject *obj = dec_struct(self, &r);
    Py_ssize_t left = r.len - r.off;
    PyBuffer_Release(&view);
    if (obj && left) {
        PyErr_Format(CodecError ? CodecError : PyExc_ValueError,
                     "%zd trailing bytes", left);
        Py_DECREF(obj);
        return NULL;
    }
    return obj;
}

static PyObject *Plan_decode_from(PlanObject *self, PyObject *args)
{
    /* mid-buffer decode for Python-plan callers with a C-plan field:
       (data, off) -> (obj, new_off); no trailing-bytes check */
    PyObject *data;
    Py_ssize_t off;
    if (!PyArg_ParseTuple(args, "On", &data, &off))
        return NULL;
    if (!self->ready) {
        RAISE("plan not initialized");
        return NULL;
    }
    Py_buffer view;
    if (PyObject_GetBuffer(data, &view, PyBUF_SIMPLE) < 0)
        return NULL;
    if (off < 0 || off > view.len) {
        PyBuffer_Release(&view);
        RAISE("offset out of range");
        return NULL;
    }
    Rd r = {view.buf, view.len, off};
    PyObject *obj = dec_struct(self, &r);
    Py_ssize_t end = r.off;
    PyBuffer_Release(&view);
    if (!obj)
        return NULL;
    PyObject *out = Py_BuildValue("(Nn)", obj, end);
    return out;
}

static PyMethodDef Plan_methods[] = {
    {"init_plan", (PyCFunction)Plan_init_plan, METH_VARARGS,
     "init_plan(cls, names, specs)"},
    {"encode", (PyCFunction)Plan_encode, METH_O, "encode(obj) -> bytes"},
    {"decode", (PyCFunction)Plan_decode, METH_O, "decode(data) -> obj"},
    {"decode_from", (PyCFunction)Plan_decode_from, METH_VARARGS,
     "decode_from(data, off) -> (obj, off)"},
    {NULL, NULL, 0, NULL},
};

static PyTypeObject Plan_Type = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "fastcodec.Plan",
    .tp_basicsize = sizeof(PlanObject),
    .tp_flags = Py_TPFLAGS_DEFAULT,
    .tp_new = Plan_new,
    .tp_dealloc = (destructor)Plan_dealloc,
    .tp_methods = Plan_methods,
};

/* ------------------------------------------------------- frame wave reader
 *
 * The RPC serving inner loop (rpc/transport.py): u32 LE payload length |
 * u32 LE header length | header | body.  The Python loop re-entered the
 * interpreter per frame (length parse, header decode, body slice — ~4
 * allocations and a dict of closures per frame).  FrameReader drains a
 * socket's whole pipelined wave in C: one recv() (GIL released), then
 * every complete frame in the buffer is parsed and header-decoded without
 * touching Python until the finished (header, body) list is returned.
 */

typedef struct {
    PyObject_HEAD
    PlanObject *plan;   /* RpcHeader plan (strong) */
    unsigned char *buf; /* unparsed bytes */
    Py_ssize_t len, cap, pos;
} FrameReaderObject;

static PyObject *FrameReader_new(PyTypeObject *type, PyObject *args,
                                 PyObject *kw)
{
    PyObject *plan;
    if (!PyArg_ParseTuple(args, "O!", &Plan_Type, &plan))
        return NULL;
    FrameReaderObject *self = (FrameReaderObject *)type->tp_alloc(type, 0);
    if (!self)
        return NULL;
    Py_INCREF(plan);
    self->plan = (PlanObject *)plan;
    self->buf = NULL;
    self->len = self->cap = self->pos = 0;
    return (PyObject *)self;
}

static void FrameReader_dealloc(FrameReaderObject *self)
{
    Py_XDECREF(self->plan);
    PyMem_Free(self->buf);
    Py_TYPE(self)->tp_free((PyObject *)self);
}

static int fr_reserve(FrameReaderObject *self, Py_ssize_t extra)
{
    /* compact consumed bytes first so the buffer stays wave-sized */
    if (self->pos) {
        memmove(self->buf, self->buf + self->pos, self->len - self->pos);
        self->len -= self->pos;
        self->pos = 0;
    }
    Py_ssize_t need = self->len + extra;
    if (need <= self->cap)
        return 0;
    Py_ssize_t cap = self->cap ? self->cap : (1 << 16);
    while (cap < need)
        cap <<= 1;
    unsigned char *np = PyMem_Realloc(self->buf, cap);
    if (!np) {
        PyErr_NoMemory();
        return -1;
    }
    self->buf = np;
    self->cap = cap;
    return 0;
}

static PyObject *FrameReader_feed(FrameReaderObject *self, PyObject *data)
{
    /* preload bytes already read elsewhere (adopted-connection leftovers
       from the partition-group router's first-frame peek) */
    Py_buffer view;
    if (PyObject_GetBuffer(data, &view, PyBUF_SIMPLE) < 0)
        return NULL;
    int rc = fr_reserve(self, view.len);
    if (rc == 0) {
        memcpy(self->buf + self->len, view.buf, view.len);
        self->len += view.len;
    }
    PyBuffer_Release(&view);
    if (rc < 0)
        return NULL;
    Py_RETURN_NONE;
}

/* parse ONE complete frame at self->pos into a (header, body) pair.
 * 1 = parsed (pair set, pos advanced), 0 = incomplete, -1 = error. */
static int fr_parse_one(FrameReaderObject *self, PyObject **pair_out)
{
    Py_ssize_t avail = self->len - self->pos;
    if (avail < 8)
        return 0;
    const unsigned char *p = self->buf + self->pos;
    uint32_t plen, hlen;
    memcpy(&plen, p, 4); /* little-endian host assumed (x86/arm) */
    memcpy(&hlen, p + 4, 4);
    if (plen < 4 || (Py_ssize_t)hlen > (Py_ssize_t)plen - 4) {
        RAISE("corrupt frame lengths");
        return -1;
    }
    if (avail < 4 + (Py_ssize_t)plen)
        return 0;
    Rd r = {p + 8, (Py_ssize_t)hlen, 0};
    PyObject *header = dec_struct(self->plan, &r);
    if (!header)
        return -1;
    if (r.off != r.len) {
        Py_DECREF(header);
        RAISE("trailing bytes after header");
        return -1;
    }
    PyObject *body = PyBytes_FromStringAndSize(
        (const char *)p + 8 + hlen, (Py_ssize_t)plen - 4 - hlen);
    if (!body) {
        Py_DECREF(header);
        return -1;
    }
    PyObject *pair = PyTuple_Pack(2, header, body);
    Py_DECREF(header);
    Py_DECREF(body);
    if (!pair)
        return -1;
    self->pos += 4 + (Py_ssize_t)plen;
    *pair_out = pair;
    return 1;
}

/* parse every complete frame at self->pos into `out`; 0 ok, -1 error */
static int fr_parse_frames(FrameReaderObject *self, PyObject *out)
{
    for (;;) {
        PyObject *pair;
        int rc = fr_parse_one(self, &pair);
        if (rc <= 0)
            return rc;
        rc = PyList_Append(out, pair);
        Py_DECREF(pair);
        if (rc < 0)
            return -1;
    }
}

/* one recv() with the GIL released into the (pre-reserved) buffer tail;
 * 0 ok (len advanced), -1 = Python error already set */
static int fr_recv(FrameReaderObject *self, long fd)
{
    if (fr_reserve(self, 1 << 18) < 0)
        return -1;
    Py_ssize_t n;
    for (;;) {
        Py_BEGIN_ALLOW_THREADS
        n = recv((int)fd, self->buf + self->len,
                 (size_t)(self->cap - self->len), 0);
        Py_END_ALLOW_THREADS
        if (n >= 0 || errno != EINTR)
            break;
        if (PyErr_CheckSignals() < 0)
            return -1;
    }
    if (n == 0) {
        PyErr_SetString(PyExc_ConnectionError, "peer closed");
        return -1;
    }
    if (n < 0) {
        PyErr_SetFromErrno(PyExc_OSError);
        return -1;
    }
    self->len += n;
    return 0;
}

static PyObject *FrameReader_read_wave(FrameReaderObject *self, PyObject *arg)
{
    long fd = PyLong_AsLong(arg);
    if (fd == -1 && PyErr_Occurred())
        return NULL;
    PyObject *out = PyList_New(0);
    if (!out)
        return NULL;
    for (;;) {
        if (fr_parse_frames(self, out) < 0) {
            Py_DECREF(out);
            return NULL;
        }
        if (PyList_GET_SIZE(out) > 0)
            return out;
        if (fr_recv(self, fd) < 0) {
            Py_DECREF(out);
            return NULL;
        }
    }
}

static PyMethodDef FrameReader_methods[] = {
    {"feed", (PyCFunction)FrameReader_feed, METH_O,
     "feed(bytes): preload already-read bytes into the buffer"},
    {"read_wave", (PyCFunction)FrameReader_read_wave, METH_O,
     "read_wave(fd) -> [(header, body), ...]; blocks for >=1 frame"},
    {NULL, NULL, 0, NULL},
};

static PyTypeObject FrameReader_Type = {
    PyVarObject_HEAD_INIT(NULL, 0)
    .tp_name = "fastcodec.FrameReader",
    .tp_basicsize = sizeof(FrameReaderObject),
    .tp_flags = Py_TPFLAGS_DEFAULT,
    .tp_new = FrameReader_new,
    .tp_dealloc = (destructor)FrameReader_dealloc,
    .tp_methods = FrameReader_methods,
};

/* ------------------------------------------------------- vectored writer */

#ifndef FC_IOV_MAX /* stay under every libc's UIO_MAXIOV (>= 1024) */
#define FC_IOV_MAX 1000
#endif

/* sendmsg_frames(fd, [(header_bytes, body), ...]) -> total bytes sent.
 * Encodes the 8-byte length prefix for every frame into one arena and
 * gathers prefix+header+body iovecs into as few sendmsg() calls as
 * IOV_MAX allows, with the GIL released for the syscalls — the whole
 * response wave leaves in one C call instead of len(wave) Python
 * send()s. */
static PyObject *sendmsg_frames(PyObject *mod, PyObject *args)
{
    long fd;
    PyObject *pairs;
    if (!PyArg_ParseTuple(args, "lO", &fd, &pairs))
        return NULL;
    PyObject *seq = PySequence_Fast(pairs, "pairs must be a sequence");
    if (!seq)
        return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    if (n == 0) {
        Py_DECREF(seq);
        return PyLong_FromLong(0);
    }
    Py_buffer *bufs = PyMem_Calloc((size_t)(2 * n), sizeof(Py_buffer));
    unsigned char *prefix = PyMem_Malloc((size_t)(8 * n));
    struct iovec *iov = PyMem_Malloc((size_t)(3 * n) * sizeof(struct iovec));
    Py_ssize_t nbufs = 0;
    PyObject *result = NULL;
    if (!bufs || !prefix || !iov) {
        PyErr_NoMemory();
        goto done;
    }
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *pair = PySequence_Fast_GET_ITEM(seq, i);
        if (!PyTuple_Check(pair) || PyTuple_GET_SIZE(pair) != 2) {
            PyErr_SetString(PyExc_TypeError,
                            "pairs items must be (header, body) tuples");
            goto done;
        }
        if (PyObject_GetBuffer(PyTuple_GET_ITEM(pair, 0), &bufs[2 * i],
                               PyBUF_SIMPLE) < 0)
            goto done;
        nbufs++;
        if (PyObject_GetBuffer(PyTuple_GET_ITEM(pair, 1), &bufs[2 * i + 1],
                               PyBUF_SIMPLE) < 0)
            goto done;
        nbufs++;
        Py_ssize_t hlen = bufs[2 * i].len, blen = bufs[2 * i + 1].len;
        Py_ssize_t plen = 4 + hlen + blen;
        if (hlen > (Py_ssize_t)UINT32_MAX || plen > (Py_ssize_t)UINT32_MAX) {
            RAISE("frame too large");
            goto done;
        }
        uint32_t w = (uint32_t)plen;
        memcpy(prefix + 8 * i, &w, 4); /* little-endian host assumed */
        w = (uint32_t)hlen;
        memcpy(prefix + 8 * i + 4, &w, 4);
        iov[3 * i].iov_base = prefix + 8 * i;
        iov[3 * i].iov_len = 8;
        iov[3 * i + 1].iov_base = bufs[2 * i].buf;
        iov[3 * i + 1].iov_len = (size_t)hlen;
        iov[3 * i + 2].iov_base = bufs[2 * i + 1].buf;
        iov[3 * i + 2].iov_len = (size_t)blen;
    }
    {
        Py_ssize_t iovcnt = 3 * n, idx = 0;
        unsigned long long total = 0;
        while (idx < iovcnt) {
            /* skip fully-consumed entries so msg_iovlen counts real work */
            if (iov[idx].iov_len == 0) {
                idx++;
                continue;
            }
            Py_ssize_t cnt = iovcnt - idx;
            if (cnt > FC_IOV_MAX)
                cnt = FC_IOV_MAX;
            struct msghdr msg;
            memset(&msg, 0, sizeof(msg));
            msg.msg_iov = iov + idx;
            msg.msg_iovlen = (size_t)cnt;
            ssize_t s;
            Py_BEGIN_ALLOW_THREADS
            s = sendmsg((int)fd, &msg, MSG_NOSIGNAL);
            Py_END_ALLOW_THREADS
            if (s < 0) {
                if (errno == EINTR) {
                    if (PyErr_CheckSignals() < 0)
                        goto done;
                    continue;
                }
                if (errno == EPIPE || errno == ECONNRESET) {
                    PyErr_SetString(PyExc_ConnectionError,
                                    "peer closed during vectored send");
                    goto done;
                }
                PyErr_SetFromErrno(PyExc_OSError);
                goto done;
            }
            total += (unsigned long long)s;
            size_t left = (size_t)s; /* advance past what the kernel took */
            while (left > 0) {
                if (iov[idx].iov_len <= left) {
                    left -= iov[idx].iov_len;
                    iov[idx].iov_len = 0;
                    idx++;
                } else {
                    iov[idx].iov_base = (char *)iov[idx].iov_base + left;
                    iov[idx].iov_len -= left;
                    left = 0;
                }
            }
        }
        result = PyLong_FromUnsignedLongLong(total);
    }
done:
    for (Py_ssize_t i = 0; i < nbufs; i++)
        PyBuffer_Release(&bufs[i]);
    PyMem_Free(iov);
    PyMem_Free(prefix);
    PyMem_Free(bufs);
    Py_DECREF(seq);
    return result;
}

/* ----------------------------------------------------------------- module */

static PyObject *register_error(PyObject *mod, PyObject *exc)
{
    Py_INCREF(exc);
    Py_XSETREF(CodecError, exc);
    Py_RETURN_NONE;
}

static PyMethodDef mod_methods[] = {
    {"register_error", register_error, METH_O,
     "register the CodecError class raised on malformed data"},
    {"sendmsg_frames", sendmsg_frames, METH_VARARGS,
     "sendmsg_frames(fd, [(header, body), ...]) -> bytes sent;\n"
     "vectored frame write with length prefixes, GIL released"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef fastcodec_module = {
    PyModuleDef_HEAD_INIT, "fastcodec",
    "C interpreter for the rpc.codec wire format", -1, mod_methods,
};

PyMODINIT_FUNC PyInit_fastcodec(void)
{
    if (PyType_Ready(&Plan_Type) < 0 || PyType_Ready(&FrameReader_Type) < 0)
        return NULL;
    PyObject *m = PyModule_Create(&fastcodec_module);
    if (!m)
        return NULL;
    Py_INCREF(&Plan_Type);
    if (PyModule_AddObject(m, "Plan", (PyObject *)&Plan_Type) < 0) {
        Py_DECREF(&Plan_Type);
        Py_DECREF(m);
        return NULL;
    }
    Py_INCREF(&FrameReader_Type);
    if (PyModule_AddObject(m, "FrameReader",
                           (PyObject *)&FrameReader_Type) < 0) {
        Py_DECREF(&FrameReader_Type);
        Py_DECREF(m);
        return NULL;
    }
    return m;
}
