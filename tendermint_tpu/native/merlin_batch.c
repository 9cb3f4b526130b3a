/* Batched schnorrkel challenges for the host side of the sr25519 verifier.
 *
 * An sr25519 signature's challenge is k = Merlin(msg, A, R) mod L: a
 * Merlin v1.0 transcript (STROBE-128 over Keccak-f[1600]) that absorbs
 * the signing context, the message, the public key and R, and squeezes
 * 64 bytes. crypto/merlin.py writes the construction out in Python
 * (1.65 ms a challenge); this file is the same construction for a chunk
 * of lanes in one call, OpenMP over lanes as sha512_batch.c, each
 * challenge reduced mod L by that file's reduce512_mod_l. The two are
 * built into one library.
 *
 * Only what the signing transcript uses is here: meta-AD, AD and PRF.
 * The transcript is exactly crypto/sr25519.py's _signing_transcript +
 * _challenge (reference crypto/sr25519/pubkey.go:49-61, the empty
 * signing context of privkey.go:18):
 *
 *   Merlin v1.0 / dom-sep "SigningContext" / "" "" / sign-bytes msg /
 *   proto-name "Schnorr-sig" / sign:pk A / sign:R R / challenge sign:c 64
 */

#include <stdint.h>
#include <string.h>

#if defined(__BYTE_ORDER__) && __BYTE_ORDER__ != __ORDER_LITTLE_ENDIAN__
#error "the STROBE state is addressed as bytes over little-endian 64-bit lanes"
#endif

/* sha512_batch.c */
void reduce512_mod_l(const uint8_t *in, int64_t n, uint8_t *out);

#define PARALLEL_MIN_BATCH 1024 /* as sha512_batch.c, and for its reason */

/* --- Keccak-f[1600] (FIPS 202) ------------------------------------------- */

static const uint64_t RC[24] = {
    0x0000000000000001ULL, 0x0000000000008082ULL, 0x800000000000808AULL,
    0x8000000080008000ULL, 0x000000000000808BULL, 0x0000000080000001ULL,
    0x8000000080008081ULL, 0x8000000000008009ULL, 0x000000000000008AULL,
    0x0000000000000088ULL, 0x0000000080008009ULL, 0x000000008000000AULL,
    0x000000008000808BULL, 0x800000000000008BULL, 0x8000000000008089ULL,
    0x8000000000008003ULL, 0x8000000000008002ULL, 0x8000000000000080ULL,
    0x000000000000800AULL, 0x800000008000000AULL, 0x8000000080008081ULL,
    0x8000000000008080ULL, 0x0000000080000001ULL, 0x8000000080008008ULL};

/* rotation offsets and lane order of the rho + pi walk from lane 1 */
static const int RHO[24] = {1,  3,  6,  10, 15, 21, 28, 36, 45, 55, 2,  14,
                            27, 41, 56, 8,  25, 43, 62, 18, 39, 61, 20, 44};
static const int PI[24] = {10, 7,  11, 17, 18, 3, 5,  16, 8,  21, 24, 4,
                           15, 23, 19, 13, 12, 2, 20, 14, 22, 9,  6,  1};

#define ROTL64(x, n) (((x) << (n)) | ((x) >> (64 - (n))))

static void keccak_f1600(uint64_t a[25]) {
  for (int round = 0; round < 24; round++) {
    uint64_t c[5], t;
    for (int x = 0; x < 5; x++)
      c[x] = a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20];
    for (int x = 0; x < 5; x++) {
      t = c[(x + 4) % 5] ^ ROTL64(c[(x + 1) % 5], 1);
      for (int y = 0; y < 25; y += 5) a[y + x] ^= t;
    }
    t = a[1];
    for (int i = 0; i < 24; i++) {
      uint64_t next = a[PI[i]];
      a[PI[i]] = ROTL64(t, RHO[i]);
      t = next;
    }
    for (int y = 0; y < 25; y += 5) {
      for (int x = 0; x < 5; x++) c[x] = a[y + x];
      for (int x = 0; x < 5; x++)
        a[y + x] = c[x] ^ (~c[(x + 1) % 5] & c[(x + 2) % 5]);
    }
    a[0] ^= RC[round];
  }
}

/* --- STROBE-128, the subset Merlin uses ---------------------------------- */

#define STROBE_R 166 /* 200 - 128/4 - 2 */
#define FLAG_I 1
#define FLAG_A 2
#define FLAG_C 4
#define FLAG_M 16

typedef struct {
  uint64_t lanes[25];
  int pos, pos_begin;
} strobe;

static uint8_t *st_bytes(strobe *s) { return (uint8_t *)s->lanes; }

static void run_f(strobe *s) {
  uint8_t *st = st_bytes(s);
  st[s->pos] ^= (uint8_t)s->pos_begin;
  st[s->pos + 1] ^= 0x04;
  st[STROBE_R + 1] ^= 0x80;
  keccak_f1600(s->lanes);
  s->pos = 0;
  s->pos_begin = 0;
}

static void absorb(strobe *s, const uint8_t *data, uint64_t len) {
  uint8_t *st = st_bytes(s);
  for (uint64_t i = 0; i < len; i++) {
    st[s->pos++] ^= data[i];
    if (s->pos == STROBE_R) run_f(s);
  }
}

static void begin_op(strobe *s, int flags) {
  uint8_t head[2] = {(uint8_t)s->pos_begin, (uint8_t)flags};
  s->pos_begin = s->pos + 1;
  absorb(s, head, 2);
  if ((flags & FLAG_C) && s->pos != 0) run_f(s);
}

/* meta-AD and AD; ``more`` continues the operation under way */
static void meta_ad(strobe *s, const uint8_t *data, uint64_t len, int more) {
  if (!more) begin_op(s, FLAG_M | FLAG_A);
  absorb(s, data, len);
}

static void ad(strobe *s, const uint8_t *data, uint64_t len) {
  begin_op(s, FLAG_A);
  absorb(s, data, len);
}

static void prf(strobe *s, uint8_t *out, int len) {
  uint8_t *st = st_bytes(s);
  begin_op(s, FLAG_I | FLAG_A | FLAG_C);
  for (int i = 0; i < len; i++) {
    out[i] = st[s->pos];
    st[s->pos++] = 0;
    if (s->pos == STROBE_R) run_f(s);
  }
}

/* --- Merlin --------------------------------------------------------------- */

static void le32(uint64_t n, uint8_t out[4]) {
  for (int i = 0; i < 4; i++) out[i] = (uint8_t)(n >> (8 * i));
}

static void append_message(strobe *s, const char *label, const uint8_t *msg,
                           uint64_t len) {
  uint8_t n[4];
  le32(len, n);
  meta_ad(s, (const uint8_t *)label, strlen(label), 0);
  meta_ad(s, n, 4, 1);
  ad(s, msg, len);
}

static void transcript_init(strobe *s, const char *label) {
  uint8_t *st = st_bytes(s);
  static const uint8_t head[6] = {1, STROBE_R + 2, 1, 0, 1, 96};
  memset(s, 0, sizeof(*s));
  memcpy(st, head, 6);
  memcpy(st + 6, "STROBEv1.0.2", 12);
  keccak_f1600(s->lanes);
  meta_ad(s, (const uint8_t *)"Merlin v1.0", 11, 0);
  append_message(s, "dom-sep", (const uint8_t *)label, strlen(label));
}

/* The challenge scalars of n sr25519 lanes: pub and r hold 32 bytes a
 * lane, message i is buf[offsets[i] .. offsets[i+1]), out takes 32
 * little-endian bytes a lane, the 64-byte challenge mod L. What every
 * lane's transcript starts with is absorbed once and copied. */
void sr25519_challenges_mod_l(const uint8_t *pub, const uint8_t *r,
                              const uint8_t *buf, const uint64_t *offsets,
                              int64_t n, uint8_t *out) {
  strobe head;
  transcript_init(&head, "SigningContext");
  append_message(&head, "", (const uint8_t *)"", 0);
#ifdef _OPENMP
#pragma omp parallel for schedule(static) if (n >= PARALLEL_MIN_BATCH)
#endif
  for (int64_t i = 0; i < n; i++) {
    strobe s = head;
    uint8_t wide[64], n64[4];
    append_message(&s, "sign-bytes", buf + offsets[i],
                   offsets[i + 1] - offsets[i]);
    append_message(&s, "proto-name", (const uint8_t *)"Schnorr-sig", 11);
    append_message(&s, "sign:pk", pub + (uint64_t)i * 32, 32);
    append_message(&s, "sign:R", r + (uint64_t)i * 32, 32);
    le32(64, n64);
    meta_ad(&s, (const uint8_t *)"sign:c", 6, 0);
    meta_ad(&s, n64, 4, 1);
    prf(&s, wide, 64);
    reduce512_mod_l(wide, 1, out + (uint64_t)i * 32);
  }
}
